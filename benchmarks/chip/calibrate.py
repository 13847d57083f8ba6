#!/usr/bin/env python3
"""Readings that set the upper end of each limit: the control and a planted
fault, at a training cell's own size. Not part of a benchmark run.

    python benchmarks/chip/calibrate.py --workload gcn-arxiv.train-powerlaw \
        --seeds 1 2 3

For each seed, put beside the cell's float32 reference (``cell.model``,
matmuls at ``highest``):

* ``control`` — the reference with every matmul, forward and backward, in
  three bf16 passes (``high``, the precision below the configuration's);
* ``half_batch`` — the reference with the loss's mean over half the nodes.

A step that returns its state unchanged reads 1 on ``grad_gap`` and
``update_gap`` by their definition and needs no run.

The first line gives each precision's error on one matmul, which shows the
precision the control ran at on this backend; then one JSON line per seed
and reading.
"""
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def train_readings(cell, seed: int) -> list:
    from benchmarks.chip import compare, graphs
    from benchmarks.chip.drivers.train import CHECKED_STEPS
    cfg = cell.config
    g = graphs.make_graph(cell.traffic["law"], cfg["num_nodes"],
                          cfg["num_edges"], cfg["num_features"],
                          cfg["num_classes"], graphs.rng_for(seed, "graph"))
    ref = cell.model.train(cfg, g, seed, steps=CHECKED_STEPS)
    out = []
    for name, kw in (("control", {"precision": "high"}),
                     ("half_batch", {"rows": g.num_nodes // 2})):
        other = cell.model.train(cfg, g, seed, steps=CHECKED_STEPS, **kw)
        out.append({"reading": name, **compare.train_numbers(other, ref)})
    return out


def matmul_errors(n: int = 512) -> dict:
    """Max relative error of an n×n product against float64, by how it is
    computed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.chip import reference
    rng = np.random.default_rng(0)
    a, b = (rng.standard_normal((n, n)).astype(np.float32) for _ in "ab")
    exact = a.astype(np.float64) @ b.astype(np.float64)
    scale = np.abs(exact).max()
    ways = {"highest": lambda: reference.matmul(a, b, "highest"),
            "high": lambda: reference.matmul(a, b, "high"),
            "high_spelt_out": lambda: reference.dot_high(a, b),
            "default": lambda: jnp.dot(a, b,
                                       precision=jax.lax.Precision.DEFAULT)}
    return {k: float(np.abs(np.asarray(f(), np.float64) - exact).max()
                     / scale) for k, f in ways.items()}


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    harness.configure_jax(cell.config)
    print(json.dumps({"matmul_rel_error": matmul_errors()}), flush=True)
    for seed in args.seeds:
        t = time.perf_counter()
        for row in train_readings(cell, seed):
            print(json.dumps({"cell": cell.name, "seed": seed, **row,
                              "s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
