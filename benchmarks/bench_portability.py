"""Fig. 9 — portability of the data-aware rules across hardware.

The paper trains the performance database on A100 and shows the rules hold
up on H100 / RTX 3090Ti. Our TPU analogue: the committed rules are fitted
under the v5e cost model; here we re-evaluate the *same* rule-selected
configs under v4 and v5p hardware constants and compare against each
generation's exhaustive best — the retention ratio is the portability
metric (paper: "consistent speedup across architectures").
"""
from __future__ import annotations

import dataclasses

from benchmarks.common import emit, geomean
from repro.core import costmodel
from repro.core.config_space import all_configs
from repro.core.costmodel import TpuSpec
from repro.core.heuristics import select_config
from repro.core.perfdb import TABLE_II

GENERATIONS = {
    "v5e": costmodel.V5E,
    "v4": TpuSpec(name="tpu_v4", peak_flops_bf16=275e12,
                  peak_flops_fp32=137.5e12, hbm_bw=1228e9,
                  vpu_flops=4 * 8 * 128 * 1.05e9, ici_bw=50e9,
                  clock=1.05e9),
    "v5p": TpuSpec(name="tpu_v5p", peak_flops_bf16=459e12,
                   peak_flops_fp32=229.5e12, hbm_bw=2765e9,
                   vpu_flops=4 * 8 * 128 * 1.75e9, ici_bw=100e9,
                   clock=1.75e9),
}

FEATS = [1, 16, 64]


def _gflops(m, v, f, cfg, spec):
    cost = costmodel.segment_reduce_cost(m, v, f, cfg, spec=spec)
    return cost.gflops(costmodel.useful_flops(m, f))


def run(quick: bool = False):
    table = TABLE_II[:4] if quick else TABLE_II
    feats = [1, 64] if quick else FEATS
    for gen, spec in GENERATIONS.items():
        ratios = []
        for name, v, m in table:
            for f in feats:
                cfg = select_config(m, v, f)        # v5e-trained rules
                ours = _gflops(m, v, f, cfg, spec)
                best = max(_gflops(m, v, f, c, spec) for c in all_configs(f))
                ratios.append(ours / best)
        emit(f"fig9/{gen}/rules_vs_native_best", 0.0,
             f"{geomean(ratios):.3f}")


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
