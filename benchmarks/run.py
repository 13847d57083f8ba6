"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (benchmarks/common.emit).

  fig6  — segment reduction vs scatter/segment_coo baselines (paper Fig. 6)
  fig7  — fused SpMM vs BCOO/unfused baselines (paper Fig. 7)
  fig8  — decision tree vs hand-crafted vs exhaustive best (paper Fig. 8)
  fig9  — rule portability across hardware generations (paper Fig. 9)
  fig10 — GCN aggregation time share (paper Fig. 10)
  fig11 — end-to-end 3-layer GNN inference (paper Fig. 11)
  roofline — §Roofline terms per (arch × shape) from the dry-run artifacts

REPRO_BENCH_QUICK=1 trims datasets/feature sweeps (CI-scale run).
"""
from __future__ import annotations

import os
import sys


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    quick = os.environ.get("REPRO_BENCH_QUICK", "0") not in ("0", "")
    from benchmarks import (bench_decision_tree, bench_end2end,
                            bench_portability, bench_segment_reduce,
                            bench_spmm, roofline)
    print("name,us_per_call,derived")
    bench_segment_reduce.run(quick=quick)
    bench_spmm.run(quick=quick)
    bench_decision_tree.run(quick=quick)
    bench_portability.run(quick=quick)
    bench_end2end.run(quick=quick)
    roofline.run(quick=quick)


if __name__ == "__main__":
    main()
