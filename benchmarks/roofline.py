"""§Roofline — three-term roofline per (arch × shape) from the compiled
dry-run (results/dryrun/*.json):

    compute_s    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory_s     = HLO_bytes_per_chip / HBM_bw
    collective_s = collective_bytes_per_chip / link_bw

Caveat handled here: XLA's cost analysis counts a while-loop body ONCE, so
scan-over-layers costs are under-reported by ~num_periods×. We correct by
**differencing**: each arch×shape is re-lowered with 1 and 2 scan periods
(scripts/run_roofline_diff.sh writes results/roofline_diff/*.json); the
difference isolates the per-period cost, and

    corrected = base_1p + (n_periods − 1) × (cell_2p − cell_1p)

MODEL_FLOPS uses 6·N·D (dense) / 6·N_active·D (MoE) + the attention
quadratic term; the ratio MODEL/HLO flags remat/redundancy waste.
"""
from __future__ import annotations

import json
import pathlib
import sys

from repro import configs as cfglib
from repro.configs import shapes as shapelib
from repro.core.costmodel import V5E
from repro.models import lm

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results"
CHIPS = {"single": 256, "multi": 512}


def model_flops_per_chip(arch: str, shape: str, chips: int) -> float:
    """Analytic useful FLOPs per chip per step (MFU denominator)."""
    cfg = cfglib.get_config(arch)
    cell = shapelib.SHAPES[shape]
    n_active = active_params(cfg)
    b, s = cell.global_batch, cell.seq_len
    if cell.kind == "train":
        tokens = b * s
        base = 6.0 * n_active * tokens
        attn = 6.0 * attn_layers(cfg) * cfg.num_heads * cfg.head_dim \
            * tokens * s            # causal ≈ S/2 keys ×2 matmuls ×3 f/b
    elif cell.kind == "prefill":
        tokens = b * s
        base = 2.0 * n_active * tokens
        attn = 2.0 * attn_layers(cfg) * cfg.num_heads * cfg.head_dim \
            * tokens * s
    else:  # decode: one token against an s-long cache
        tokens = b
        base = 2.0 * n_active * tokens
        attn = 4.0 * attn_layers(cfg) * cfg.num_heads * cfg.head_dim \
            * tokens * s
    return (base + attn) / chips


def attn_layers(cfg) -> int:
    return sum(1 for i in range(cfg.num_layers) if cfg.is_attn_layer(i))


def active_params(cfg) -> float:
    """Parameters touched per token (MoE: top-k + shared experts only)."""
    d = cfg.d_model
    n = 2.0 * cfg.padded_vocab * d if not cfg.tie_embeddings \
        else cfg.padded_vocab * d
    per_expert = (3 if cfg.mlp_gated else 2) * d * (cfg.moe_d_ff or cfg.d_ff)
    for i in range(cfg.num_layers):
        if cfg.rwkv:
            n += 5 * d * d + 3 * d * cfg.d_ff
        elif cfg.is_attn_layer(i):
            n += d * (cfg.q_dim + 2 * cfg.kv_dim) + cfg.q_dim * d
        else:  # mamba
            di = cfg.expand * d
            n += 2 * d * di + di * d + di * (d // 16 + 2 * cfg.d_state)
        if not cfg.rwkv:
            if cfg.is_moe_layer(i):
                n += cfg.top_k * per_expert \
                    + cfg.num_shared_experts * per_expert + d * cfg.num_experts
            else:
                n += (3 if cfg.mlp_gated else 2) * d * cfg.d_ff
    return float(n)


def corrected_terms(cell_json: dict, diff: dict | None):
    """Per-chip (flops, bytes, collective bytes).

    With differencing data: corrected = 1p + (n_periods − 1)·(2p − 1p),
    where the kp lowers are *unrolled* (fully counted). Without it, the raw
    full-cell numbers are returned (scan bodies counted once — a lower
    bound, flagged via `corrected=False`)."""
    flops = cell_json["cost_analysis"].get("flops", 0.0)
    byts = cell_json["cost_analysis"].get("bytes accessed", 0.0)
    coll = float(cell_json["collectives"]["total_bytes"])
    if diff and diff.get("status") == "ok":
        n_per = max(diff["n_periods_full"], 1)
        flops = diff["flops_1p"] + (n_per - 1) * max(
            diff["flops_2p"] - diff["flops_1p"], 0.0)
        byts = diff["bytes_1p"] + (n_per - 1) * max(
            diff["bytes_2p"] - diff["bytes_1p"], 0.0)
        coll = diff["coll_1p"] + (n_per - 1) * max(
            diff["coll_2p"] - diff["coll_1p"], 0.0)
    return flops, byts, coll


def load(arch, shape, mesh="single"):
    f = RESULTS / "dryrun" / f"{arch}__{shape}__{mesh}.json"
    if not f.exists():
        return None
    return json.loads(f.read_text())


def load_diff(arch, shape, mesh="single"):
    f = RESULTS / "roofline_diff" / f"{arch}__{shape}__{mesh}.json"
    if not f.exists():
        return None
    return json.loads(f.read_text())


def roofline_row(arch, shape, mesh="single", dtype_bytes=2):
    cell = load(arch, shape, mesh)
    if cell is None or cell.get("status") != "ok":
        return None
    diff = load_diff(arch, shape, mesh)
    flops, byts, coll = corrected_terms(cell, diff)
    peak = V5E.peak_flops_bf16 if dtype_bytes == 2 else V5E.peak_flops_fp32
    compute_s = flops / peak
    memory_s = byts / V5E.hbm_bw
    coll_s = coll / V5E.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    mflops = model_flops_per_chip(arch, shape, CHIPS[mesh])
    total = max(compute_s, memory_s, coll_s)
    return {
        "arch": arch, "shape": shape, "mesh": mesh,
        "flops": flops, "bytes": byts, "coll_bytes": coll,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "bottleneck": bottleneck,
        "model_flops": mflops,
        "model_over_hlo": mflops / max(flops, 1.0),
        "mfu_bound": mflops / peak / max(total, 1e-12),
        "corrected": bool(diff and diff.get("status") == "ok"),
    }


def run(quick: bool = False):
    from benchmarks.common import emit
    rows = []
    for arch in cfglib.ARCH_NAMES:
        cfg = cfglib.get_config(arch)
        for shape in shapelib.SHAPE_NAMES:
            if shapelib.cell_applicable(cfg, shape):
                continue
            r = roofline_row(arch, shape)
            if r is None:
                continue
            rows.append(r)
            emit(f"roofline/{arch}/{shape}", r["compute_s"] * 1e6,
                 f"mem={r['memory_s']*1e6:.0f}us|coll={r['collective_s']*1e6:.0f}us|"
                 f"bound={r['bottleneck']}|mfu_bound={r['mfu_bound']:.2f}|"
                 f"corr={int(r['corrected'])}")
    out = RESULTS / "roofline.json"
    out.write_text(json.dumps(rows, indent=1))
    return rows


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
