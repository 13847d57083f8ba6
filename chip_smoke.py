#!/usr/bin/env python3
"""Smoke run of the GNN main path on a TPU, through the entry points a user
calls. One process; it starts no child.

With no arguments it needs one chip and runs, in order:

1. device — platform, device kind and count;
2. train  — the repro trainer (what ``repro.fit`` runs) on OGB's reference
   GCN for ogbn-arxiv (3 layers, hidden 256, lr 0.01) over a graph of
   ogbn-arxiv's shape (169,343 nodes, 1,166,243 edges, 128 features, 40
   classes) made from ``--seed``. Checks: finite losses that fall, one
   trace, a ``tpu_custom_call`` in the compiled step for every Pallas kernel
   the step launched, and the Pallas forward equal to the jnp reference;
3. serve  — a ``GNNServer`` at the same widths, warmed on its buckets,
   answering 16 graphs of 64-4096 nodes; each answer equals the reference
   forward of that graph, and serving compiles nothing after warmup;
4. memory — peak device memory.

``--chips 4`` runs only the sharded path: the same training over a 4-device
mesh against single-device training from the same parameters and batches.

All matmuls run at full fp32 precision (``highest``), so every comparison is
like for like. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed; any
failure, or a backend other than a TPU, exits non-zero without it.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import collections
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# ogbn-arxiv (Table II of the paper / OGB) and OGB's reference GCN for it
ARXIV = dict(nodes=169_343, edges=1_166_243, feat=128, classes=40)
GCN = dict(layers=3, hidden=256, lr=0.01)
TOL = 1e-3      # max |got - ref| / max |ref|
KERNELS = ("gather_segment_reduce", "fused_transform_reduce",
           "segment_reduce", "segment_softmax", "segment_matmul", "sddmm")


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def diff(got, want) -> tuple:
    """(max |got - want|, that over max |want|)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    d = float(np.max(np.abs(got - want))) if want.size else 0.0
    scale = float(np.max(np.abs(want))) if want.size else 0.0
    return d, d / max(scale, 1e-30)


def launched_kernels(fusion_counts) -> set:
    """Kernel names behind the trace-time launch accounting
    (``fused:<op>`` keys, where op starts with its kernel's name)."""
    out = set()
    for key in fusion_counts:
        kind, _, op = key.partition(":")
        if kind == "fused":
            out.add(max((k for k in KERNELS if op.startswith(k)), key=len))
    return out


def custom_calls(hlo_text: str) -> collections.Counter:
    """tpu_custom_call instructions of a compiled HLO text, by kernel name."""
    pat = re.compile(r"%([A-Za-z_]+)(?:\.\d+)? = .*"
                     r'custom_call_target="tpu_custom_call"')
    return collections.Counter(m.group(1) for m in pat.finditer(hlo_text))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase() -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    return info


def train_phase(*, nodes, edges, feat, classes, hidden, layers, lr, steps,
                seed, mesh=None) -> dict:
    """Train GCN on one seeded graph; returns losses, traces, the launched
    kernels, the step's compiled HLO text and the trained params."""
    from repro import GraphEpochProvider, NodeClassification, Trainer
    from repro import TrainerConfig
    from repro.kernels.ops import fusion_scope
    from repro.optim.adamw import AdamWConfig

    t0 = time.perf_counter()
    data = GraphEpochProvider(shapes=((nodes, edges),), graphs_per_shape=1,
                              feat=feat, num_classes=classes, seed=seed)
    task = NodeClassification.from_provider(data, model="gcn", hidden=hidden,
                                            num_layers=layers, impl="pallas")
    cfg = TrainerConfig(steps=steps, seed=seed, warmup_steps=0,
                        lr_schedule="constant",
                        opt=AdamWConfig(lr=lr, weight_decay=0.0))
    trainer = Trainer(task, data, cfg, mesh=mesh, tune=False)
    setup_s = time.perf_counter() - t0

    stamps = [time.perf_counter()]
    with fusion_scope() as launches:
        res = trainer.fit(
            metrics_cb=lambda *_: stamps.append(time.perf_counter()))
    step_s = np.diff(stamps)
    arrays, static = task.prepare(data.batch(0), tune=False, mesh=mesh)
    traces = res.traces
    hlo = trainer.executable(static).lower(res.state, arrays) \
        .compile().as_text()
    return dict(losses=res.losses, traces=traces, setup_s=setup_s,
                step_s=step_s, launched=launched_kernels(launches), hlo=hlo,
                params=res.state.params, arrays=arrays, static=static,
                graph=data.batch(0))


def forward_parity(tr: dict) -> tuple:
    """The trained params' Pallas forward against the jnp reference."""
    import jax

    from repro.models import gnn

    a, static = tr["arrays"], tr["static"]

    def fwd(impl):
        return jax.jit(lambda p, a: gnn.forward(
            p, "gcn", a["x"], a["edge_index"], static.num_nodes,
            a["deg_inv_sqrt"], impl, a["plan"]))(tr["params"], a)

    return diff(fwd("pallas"), fwd("ref"))


def serve_phase(params, *, feat, classes, num_graphs=16, min_nodes=64,
                max_nodes=4096, avg_degree=8, seed=0) -> dict:
    """Warm a GNNServer on its buckets, serve ``num_graphs`` graphs, and
    compare each answer with the reference forward of that graph."""
    from repro import GNNServer, synth_graph
    from repro.models import gnn
    from repro.serve.buckets import bucket_for

    sizes = np.geomspace(min_nodes, max_nodes, num_graphs).round()
    graphs = [synth_graph(f"request-{i}", int(v), int(v) * avg_degree,
                          feat=feat, num_classes=classes, seed=seed + i)
              for i, v in enumerate(sizes)]
    # one graph per micro-batch, so the buckets to warm are known up front;
    # an empty PerfDB keeps every config on the committed rules
    server = GNNServer(params, "gcn", impl="pallas", tune=False, perfdb={},
                       max_batch_graphs=1)
    buckets = sorted({bucket_for(g.num_nodes, g.num_edges, server.policy)
                      for g in graphs},
                     key=lambda b: (b.num_nodes, b.num_edges))
    t0 = time.perf_counter()
    server.warmup(buckets)
    warmup_s = time.perf_counter() - t0
    warm_compiles = server.compiles
    uids = [server.submit(g) for g in graphs]
    results = server.run_until_drained()
    worst = (0.0, 0.0)
    for uid, g in zip(uids, graphs):
        ref = gnn.forward(params, "gcn", g.x, g.edge_index, g.num_nodes,
                          g.deg_inv_sqrt, impl="ref")
        worst = max(worst, diff(results[uid].logits, ref), key=lambda t: t[1])
    stats = server.stats()
    return dict(graphs=len(graphs), buckets=len(buckets), warmup_s=warmup_s,
                warm_compiles=warm_compiles, compiles=server.compiles,
                served=len(results), hit_rate=stats["cache"]["hit_rate"],
                p50_s=float(np.median([r.latency_s
                                       for r in results.values()])),
                max_abs=worst[0], max_rel=worst[1])


def memory_phase() -> dict:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {"peak_bytes": stats.get("peak_bytes_in_use"),
            "limit_bytes": stats.get("bytes_limit")}


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------

def check_training(tr: dict, *, expect_kernels: bool) -> None:
    losses = tr["losses"]
    log(f"[train] losses={[round(x, 6) for x in losses]} "
        f"traces={tr['traces']}")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(tr["traces"] == 1, f"{tr['traces']} traces, expected 1")
    calls = custom_calls(tr["hlo"])
    log(f"[train] kernels launched={sorted(tr['launched'])} "
        f"tpu_custom_call={dict(calls)}")
    check(bool(tr["launched"]), "the step launched no Pallas kernel")
    if expect_kernels:
        missing = tr["launched"] - set(calls)
        check(not missing, f"no tpu_custom_call for {sorted(missing)}")


def run_one_chip(args) -> None:
    tr = train_phase(nodes=ARXIV["nodes"], edges=ARXIV["edges"],
                     feat=ARXIV["feat"], classes=ARXIV["classes"],
                     hidden=GCN["hidden"], layers=GCN["layers"],
                     lr=GCN["lr"], steps=args.steps, seed=args.seed)
    g = tr["graph"]
    log(f"[train] graph nodes={g.num_nodes} edges={g.num_edges} "
        f"widths={ARXIV['feat']}->{GCN['hidden']}->{GCN['hidden']}->"
        f"{ARXIV['classes']} setup_s={tr['setup_s']:.3f}")
    log(f"[train] step_s={[round(float(s), 4) for s in tr['step_s']]} "
        f"(the first includes compilation)")
    check_training(tr, expect_kernels=True)
    d_abs, d_rel = forward_parity(tr)
    log(f"[train] pallas vs ref forward: max_abs={d_abs:.3e} "
        f"max_rel={d_rel:.3e} tol={TOL:g}")
    check(d_rel <= TOL, f"pallas forward off the reference by {d_rel:.3e}")

    sv = serve_phase(tr["params"], feat=ARXIV["feat"],
                     classes=ARXIV["classes"], seed=args.seed)
    log(f"[serve] graphs={sv['graphs']} buckets={sv['buckets']} "
        f"warmup_s={sv['warmup_s']:.3f} compiles={sv['compiles']} "
        f"hit_rate={sv['hit_rate']} p50_s={sv['p50_s']:.6f}")
    log(f"[serve] served vs ref: max_abs={sv['max_abs']:.3e} "
        f"max_rel={sv['max_rel']:.3e} tol={TOL:g}")
    check(sv["served"] == sv["graphs"], "not every request was answered")
    check(sv["compiles"] == sv["warm_compiles"],
          "serving compiled after warmup")
    check(sv["max_rel"] <= TOL, f"served logits off by {sv['max_rel']:.3e}")

    mem = memory_phase()
    log(f"[memory] peak_bytes_in_use={mem['peak_bytes']} "
        f"bytes_limit={mem['limit_bytes']}")
    check(mem["peak_bytes"] is not None, "no peak memory reported")


def run_sharded(args) -> None:
    from repro.core.dist_mp import make_shard_mesh

    common = dict(nodes=ARXIV["nodes"], edges=ARXIV["edges"],
                  feat=ARXIV["feat"], classes=ARXIV["classes"],
                  hidden=GCN["hidden"], layers=GCN["layers"], lr=GCN["lr"],
                  steps=args.steps, seed=args.seed)
    sharded = train_phase(mesh=make_shard_mesh(args.chips), **common)
    log(f"[sharded] shards={args.chips} step_s="
        f"{[round(float(s), 4) for s in sharded['step_s']]}")
    check_training(sharded, expect_kernels=True)
    single = train_phase(**common)
    log(f"[single] step_s={[round(float(s), 4) for s in single['step_s']]}")
    check_training(single, expect_kernels=True)
    d_abs, d_rel = diff(sharded["losses"], single["losses"])
    log(f"[sharded] loss vs single device: max_abs={d_abs:.3e} "
        f"max_rel={d_rel:.3e} tol={TOL:g}")
    check(d_rel <= TOL, f"sharded losses off by {d_rel:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{jax.devices()[0].platform!r}", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" JAX found {len(jax.devices())}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    log(f"[setup] compile cache at {enable_compile_cache()}; "
        f"matmul precision highest")
    jax.config.update("jax_default_matmul_precision", "highest")
    info = device_phase()
    if args.chips == 1:
        run_one_chip(args)
    else:
        run_sharded(args)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
