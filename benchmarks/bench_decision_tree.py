"""Fig. 8 — decision-tree rules vs hand-crafted rules vs exhaustive best.

For each (dataset × F): v5e cost-model GFlops of the config chosen by
  hand  — static engineering rule (paper's Fig. 8 baseline)
  tree  — the codegen'd decision-tree rules (ours)
  best  — exhaustive sweep of the pruned space (oracle upper bound)

The paper's claim: tree ≈ best ≫ hand. Also measures rule-selection
latency (must be ~ns-scale: if/else only).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, geomean
from repro.core import costmodel
from repro.core.config_space import all_configs
from repro.core.heuristics import hand_crafted_config, select_config
from repro.core.perfdb import TABLE_II

FEATS = [1, 4, 16, 32, 64, 128]


def _gflops(m, v, f, cfg):
    return costmodel.segment_reduce_cost(m, v, f, cfg).gflops(
        costmodel.useful_flops(m, f))


def run(quick: bool = False):
    table = TABLE_II[:4] if quick else TABLE_II
    feats = [1, 32] if quick else FEATS
    ratios_tree, ratios_hand = [], []
    for name, v, m in table:
        for f in feats:
            best = max(_gflops(m, v, f, c) for c in all_configs(f))
            tree = _gflops(m, v, f, select_config(m, v, f))
            hand = _gflops(m, v, f, hand_crafted_config(m, v, f))
            ratios_tree.append(tree / best)
            ratios_hand.append(hand / best)
            emit(f"fig8/{name}/F{f}", 0.0,
                 f"tree={tree:.1f}|hand={hand:.1f}|best={best:.1f}GFLOPs")
    emit("fig8/tree_vs_best_geomean", 0.0, f"{geomean(ratios_tree):.3f}")
    emit("fig8/hand_vs_best_geomean", 0.0, f"{geomean(ratios_hand):.3f}")

    # rule-selection overhead (paper: nanoseconds — pure if/else dispatch)
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        select_config(1_000_000 + i, 100_000, 32)
    dt = (time.perf_counter() - t0) / n
    emit("fig8/rule_selection_overhead", dt * 1e6, f"{dt*1e9:.0f}ns/call")


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
