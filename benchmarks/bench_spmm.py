"""Fig. 7 — SpMM (fused message+aggregate) vs sparse baselines.

Baselines:
  bcoo      — jax.experimental.sparse BCOO @ dense (cuSPARSE analogue)
  unfused   — gather → weight → sorted segment_sum (Listing 2 upper path)
  geot      — index_weight_segment_reduce, blocked, tree config (ours, §IV)

derived: speedup_vs_bcoo | v5e cost-model GFlops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from benchmarks.common import emit, geomean, timeit
from repro.core import costmodel, ops
from repro.core.heuristics import select_config
from repro.data.graphs import dataset

DATASETS = ["citeseer", "cora", "ppi", "pubmed", "amazon-photo", "flickr"]
FEATS = [16, 32, 64, 128]


def run(quick: bool = False):
    datasets = DATASETS[:4] if quick else DATASETS
    feats = [16, 64] if quick else FEATS
    rng = np.random.default_rng(0)
    speedups = []
    for name in datasets:
        g = dataset(name, feat=1)
        src = jnp.asarray(g.edge_index[0])
        dst = jnp.asarray(g.edge_index[1])
        m, v = g.num_edges, g.num_nodes
        w = jnp.asarray(rng.standard_normal(m).astype(np.float32))
        coo = jsparse.BCOO(
            (w, jnp.stack([dst, src], axis=1)), shape=(v, v))

        for f in feats:
            h = jnp.asarray(rng.standard_normal((v, f), np.float32))
            bcoo_mm = jax.jit(lambda h: coo @ h)
            unfused = jax.jit(lambda h: jax.ops.segment_sum(
                jnp.take(h, src, axis=0) * w[:, None], dst, v,
                indices_are_sorted=True))
            cfg = select_config(m, v, f)
            from repro.core.config_space import KernelConfig
            cfg_cpu = KernelConfig("SR", cfg.s_b, cfg.n_b, cfg.m_b, 1)
            geot = jax.jit(lambda h: ops.index_weight_segment_reduce(
                h, src, w, dst, v, impl="blocked", config=cfg_cpu))

            t_bcoo = timeit(bcoo_mm, h, reps=3)
            t_unf = timeit(unfused, h, reps=3)
            t_geot = timeit(geot, h, reps=3)
            cost = costmodel.spmm_cost(m, v, f, cfg)
            gflops = cost.gflops(2.0 * costmodel.useful_flops(m, f))
            sp = t_bcoo / t_geot
            speedups.append(sp)
            emit(f"fig7/{name}/F{f}/bcoo", t_bcoo, "1.00x")
            emit(f"fig7/{name}/F{f}/unfused", t_unf,
                 f"{t_bcoo / t_unf:.2f}x")
            emit(f"fig7/{name}/F{f}/geot_fused", t_geot,
                 f"{sp:.2f}x|v5e_model={gflops:.1f}GFLOPs")
    emit("fig7/geomean_speedup_vs_bcoo", 0.0, f"{geomean(speedups):.2f}x")


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
