"""Fig. 11 — end-to-end GNN inference (3-layer GCN/GIN/GraphSAGE) and
Fig. 10 — CUDA-time-breakdown analogue (aggregation share of runtime).

Modes (paper §V-B4):
  dense  — PyG dense mode analogue: normalized dense adjacency matmul
  sparse — PyG sparse mode analogue: BCOO SpMM aggregation
  geot   — fused index_(weight_)segment_reduce aggregation (ours)

derived: speedup vs sparse | aggregation share (fig10).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from benchmarks.common import emit, geomean, timeit
from repro.core.plan import make_graph_plan
from repro.data.graphs import dataset
from repro.models import gnn

DATASETS = {"flickr": 0.3, "ogbn-arxiv": 0.3, "reddit2": 0.03}
MODELS = ["gcn", "gin", "sage"]
HIDDEN = [32, 64]
REPS = 3


def _model_with_agg(model, params, agg_fn, x, num_nodes):
    """Run the 3-layer model with a pluggable aggregation implementation."""
    h = x
    for i, prm in enumerate(params):
        if model == "gcn":
            hw = h @ prm["w"].value
            h2 = agg_fn(hw, weighted=True) + prm["b"].value
        elif model == "gin":
            agg = agg_fn(h, weighted=False)
            z = (1.0 + prm["eps"].value) * h + agg
            z = jax.nn.relu(z @ prm["mlp1"].value + prm["b1"].value)
            h2 = z @ prm["mlp2"].value + prm["b2"].value
        else:
            agg = agg_fn(h, weighted=False, mean=True)
            h2 = (h @ prm["w_self"].value + agg @ prm["w_neigh"].value
                  + prm["b"].value)
        h = jax.nn.relu(h2) if i < len(params) - 1 else h2
    return h


def run(quick: bool = False):
    rng = np.random.default_rng(0)
    datasets = dict(list(DATASETS.items())[:2]) if quick else DATASETS
    hidden = [32] if quick else HIDDEN
    sp_all = {"dense": [], "geot": []}
    for name, scale in datasets.items():
        g = dataset(name, feat=32, scale=scale)
        v, m = g.num_nodes, g.num_edges
        src = jnp.asarray(g.edge_index[0])
        dst = jnp.asarray(g.edge_index[1])
        dis = jnp.asarray(g.deg_inv_sqrt)
        w = dis[src] * dis[dst]
        coo = jsparse.BCOO((w, jnp.stack([dst, src], 1)), shape=(v, v))
        coo_u = jsparse.BCOO((jnp.ones_like(w), jnp.stack([dst, src], 1)),
                             shape=(v, v))
        deg = jnp.maximum(jax.ops.segment_sum(
            jnp.ones((m,)), dst, v, indices_are_sorted=True), 1.0)
        dense_a = None
        if v <= 20_000:      # PyG-dense analogue only where V² fits memory
            a = np.zeros((v, v), np.float32)
            np.add.at(a, (np.asarray(dst), np.asarray(src)),
                      np.asarray(w))
            dense_a = jnp.asarray(a)

        def agg_sparse(h, weighted=False, mean=False):
            y = (coo if weighted else coo_u) @ h
            return y / deg[:, None] if mean else y

        # one plan per graph, shared by every layer / model / hidden width
        plan = make_graph_plan(g.edge_index, v, feat=max(HIDDEN))

        def agg_geot(h, weighted=False, mean=False):
            from repro.core import ops
            if weighted:
                return ops.index_weight_segment_reduce(h, src, w, dst, v,
                                                       impl="blocked",
                                                       plan=plan)
            return ops.index_segment_reduce(
                h, src, dst, v, reduce="mean" if mean else "sum",
                impl="blocked" if not mean else "ref",
                plan=plan if not mean else None)

        def agg_dense(h, weighted=False, mean=False):
            y = dense_a @ h if weighted else (dense_a != 0) @ h
            return y / deg[:, None] if mean else y

        for model in MODELS:
            for hdim in hidden:
                params = gnn.init(jax.random.PRNGKey(0), model, 32, hdim, 16)
                x = jnp.asarray(rng.standard_normal((v, 32), np.float32))
                run_with = lambda agg: jax.jit(functools.partial(
                    _model_with_agg, model, params, agg, num_nodes=v))
                t_sparse = timeit(run_with(agg_sparse), x, reps=3)
                t_geot = timeit(run_with(agg_geot), x, reps=3)
                emit(f"fig11/{name}/{model}/H{hdim}/sparse", t_sparse, "1.00x")
                emit(f"fig11/{name}/{model}/H{hdim}/geot", t_geot,
                     f"{t_sparse / t_geot:.2f}x")
                sp_all["geot"].append(t_sparse / t_geot)
                if dense_a is not None:
                    t_dense = timeit(run_with(agg_dense), x, reps=3)
                    emit(f"fig11/{name}/{model}/H{hdim}/dense", t_dense,
                         f"{t_sparse / t_dense:.2f}x")
                    sp_all["dense"].append(t_sparse / t_dense)

                # Fig. 10 breakdown: aggregation share of total runtime,
                # timed at each layer's actual width (H, H, out-classes)
                if model == "gcn":
                    from repro.core import ops
                    widths = [hdim, hdim, 16]
                    t_sp = t_ge = 0.0
                    for width in widths:
                        hw = jnp.asarray(rng.standard_normal(
                            (v, width), np.float32))
                        t_sp += timeit(jax.jit(lambda h: coo @ h), hw,
                                       reps=3)
                        t_ge += timeit(jax.jit(
                            lambda h: ops.index_weight_segment_reduce(
                                h, src, w, dst, v, impl="blocked")), hw,
                            reps=3)
                    emit(f"fig10/{name}/H{hdim}/agg_share_sparse", t_sp,
                         f"{min(100.0, 100*t_sp/max(t_sparse,1e-9)):.1f}%")
                    emit(f"fig10/{name}/H{hdim}/agg_share_geot", t_ge,
                         f"{min(100.0, 100*t_ge/max(t_geot,1e-9)):.1f}%")
    emit("fig11/geomean_speedup_vs_sparse", 0.0,
         f"geot={geomean(sp_all['geot']):.2f}x")


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
