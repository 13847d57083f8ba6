#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip this process holds.

    python benchmarks/chip/run.py --workload gcn-arxiv.train-powerlaw \
        --seed 1234 --seconds 20 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from benchmarks.chip.harness import main
    sys.exit(main(t0=T0))
