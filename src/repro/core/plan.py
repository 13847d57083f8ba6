"""Precomputed reduction plans (GeoT §III-C data-awareness, amortized).

A :class:`SegmentPlan` captures, *once per graph*, everything the Pallas
segment-reduction kernels otherwise derive on every call:

  * ``chunk_first`` / ``chunk_count`` — the per-output-block chunk range over
    the padded input-row space (the scalar-prefetched schedule metadata);
  * a **tight** ``max_chunks`` — the maximum number of input chunks actually
    owned by any output block. The plan-less path must assume the worst case
    (``m_pad // m_b``: one block owns every row), so the kernel grid's chunk
    dimension is O(M / m_b); with a plan it is O(actual skew);
  * degree statistics of the segment index (for the data-aware heuristic /
    decision-tree config selection, paper Fig. 5);
  * the selected :class:`~repro.core.config_space.KernelConfig`.

Plans are registered pytrees: the chunk arrays are leaves (device arrays,
jit/vmap/grad-transparent) while sizes, the config, and the statistics are
static aux data — so a plan threads through ``jax.jit`` boundaries without
retriggering compilation as long as the *schedule* is unchanged.

Build a plan with :func:`make_plan` (raw sorted index) or
:func:`make_graph_plan` (``edge_index`` convention of the GNN stack), then
pass it to ``segment_reduce`` / ``index_segment_reduce`` /
``index_weight_segment_reduce`` via ``plan=``. FASTEN (ICS'24) measures that
exactly this amortization — metadata built once, reused across layers and
training steps — is where fused segment ops win end-to-end; see
``docs/plans.md``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config_space import KernelConfig
from repro.obs import span

__all__ = ["SegmentStats", "SegmentPlan", "PartitionedPlan", "RelationPlan",
           "make_plan", "make_graph_plan", "make_partitioned_plan",
           "make_relation_plan"]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class SegmentStats:
    """O(|V|) degree statistics of a sorted segment index (static metadata)."""
    num_rows: int            # M = |E| (index length)
    num_segments: int        # S (output rows)
    live_segments: int       # segments with >= 1 row (gapped ids shrink this)
    max_degree: int          # heaviest segment
    avg_degree: float        # M / max(live_segments, 1)
    std_degree: float        # over live segments

    @property
    def skew(self) -> float:
        """max/avg degree — the load-imbalance the tight grid exploits."""
        return self.max_degree / max(self.avg_degree, 1e-9)


def segment_stats(idx: np.ndarray, num_segments: int) -> SegmentStats:
    idx = np.asarray(idx)
    m = int(idx.size)
    if m == 0:
        return SegmentStats(0, num_segments, 0, 0, 0.0, 0.0)
    deg = np.bincount(idx, minlength=num_segments)
    live = deg[deg > 0]
    return SegmentStats(
        num_rows=m,
        num_segments=num_segments,
        live_segments=int(live.size),
        max_degree=int(deg.max()),
        avg_degree=float(m / max(live.size, 1)),
        std_degree=float(live.std()) if live.size else 0.0,
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SegmentPlan:
    """Precomputed schedule for one (sorted idx, num_segments) instance.

    Leaves: ``chunk_first`` / ``chunk_count`` (int32, shape (out_blocks,)).
    Aux (static): sizes, the tight ``max_chunks``, the selected ``config``,
    and :class:`SegmentStats`.
    """
    chunk_first: jax.Array
    chunk_count: jax.Array
    num_rows: int
    num_segments: int
    max_chunks: int          # tight: max(chunk_count), >= 1
    config: KernelConfig
    stats: SegmentStats

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.chunk_first, self.chunk_count)
        aux = (self.num_rows, self.num_segments, self.max_chunks,
               self.config, self.stats)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        chunk_first, chunk_count = children
        num_rows, num_segments, max_chunks, config, stats = aux
        return cls(chunk_first, chunk_count, num_rows, num_segments,
                   max_chunks, config, stats)

    # -- introspection ------------------------------------------------------
    @property
    def worst_case_chunks(self) -> int:
        """The chunk-grid bound the plan-less kernel must assume."""
        return _round_up(max(self.num_rows, 1), self.config.m_b) // self.config.m_b

    @property
    def grid_savings(self) -> float:
        """worst-case / tight chunk-dim ratio (>= 1; higher = more skew won)."""
        return self.worst_case_chunks / max(self.max_chunks, 1)

    def pin_worst_case(self) -> "SegmentPlan":
        """The same plan with ``max_chunks`` pinned to the shape-static
        worst case — the canonicalization every bucket-reuse path (serving
        templates, per-bucket train steps, sampled batches) applies so
        that plans for *different* graphs padded to one (M, S) shape share
        a treedef and never retrace the executable. Returns ``self`` when
        already pinned; the tight bound is recoverable only by replanning
        (it is data, not shape)."""
        if self.max_chunks == self.worst_case_chunks:
            return self
        return dataclasses.replace(self, max_chunks=self.worst_case_chunks)

    def validate(self, num_rows: int, num_segments: int) -> None:
        """Trace-time consistency check against the arrays of an op call."""
        if num_rows != self.num_rows or num_segments != self.num_segments:
            raise ValueError(
                f"SegmentPlan built for (M={self.num_rows}, "
                f"S={self.num_segments}) used with (M={num_rows}, "
                f"S={num_segments}); rebuild the plan for this graph.")


def make_plan(idx, num_segments: int, feat: int = 128,
              config: Optional[KernelConfig] = None,
              tune: Optional[bool] = None) -> SegmentPlan:
    """Build a :class:`SegmentPlan` from a *concrete* sorted segment index.

    ``idx`` must be host-available (numpy or committed jax array) — plans are
    built once per graph outside jit, then reused inside it. ``feat`` is the
    representative feature width fed to the config heuristic (use the widest
    layer width; only the selected config depends on it, not correctness).

    ``tune=True`` engages the wall-clock autotuner as the top selection tier
    (measured sweep, cached per shape class in the
    :class:`~repro.core.autotune.PerfDB`); ``tune=None`` defers to the
    ``REPRO_AUTOTUNE`` env var. Plan construction is the natural place to
    pay the one-off tuning cost: it already runs once per graph, outside jit.
    """
    idx_np = np.asarray(idx).astype(np.int32)
    if idx_np.ndim != 1:
        raise ValueError(f"idx must be 1-D, got shape {idx_np.shape}")
    if idx_np.size and np.any(idx_np[1:] < idx_np[:-1]):
        raise ValueError("idx must be sorted non-decreasing")
    m = int(idx_np.size)
    with span("plan.build", num_edges=m) as sp:
        stats = segment_stats(idx_np, num_segments)

        if config is None:
            from repro.core.heuristics import select_config
            # data-aware selection: the *live* segment count drives avg
            # degree, so gapped ids (batched / masked graphs) do not dilute
            # the feature
            config = select_config(max(m, 1), max(stats.live_segments, 1),
                                   feat, tune=tune)

        s_b, m_b = config.s_b, config.m_b
        m_pad = _round_up(max(m, 1), m_b)
        idxp = np.full((m_pad,), num_segments, np.int32)
        idxp[:m] = idx_np

        # the kernel's own metadata helper, evaluated concretely on the
        # host — one formula, so plans can never drift from the per-call
        # path
        from repro.kernels.segment_reduce import chunk_metadata
        chunk_first, chunk_count = chunk_metadata(idxp, num_segments, s_b,
                                                  m_b, m_pad)
        chunk_count_np = np.asarray(chunk_count)
        max_chunks = (max(1, int(chunk_count_np.max()))
                      if chunk_count_np.size else 1)
        # the tight grid beside the worst case a bucket-pinned plan walks
        sp.set(out_blocks=int(chunk_count_np.size),
               chunks_owned=int(chunk_count_np.sum()), max_chunks=max_chunks,
               worst_case_chunks=m_pad // m_b,
               config=f"{config.schedule} s_b={s_b} m_b={m_b}")
    return SegmentPlan(
        chunk_first=jnp.asarray(chunk_first),
        chunk_count=jnp.asarray(chunk_count),
        num_rows=m,
        num_segments=int(num_segments),
        max_chunks=max_chunks,
        config=config,
        stats=stats,
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PartitionedPlan:
    """Per-shard :class:`SegmentPlan` metadata with **stacked** leaves, so
    the whole plan rides ``shard_map`` with ``PartitionSpec("shard")``.

    All shards share one static program: a common ``config``, a common
    padded row count (``num_rows = edges_per_shard``), the *global* segment
    space (``num_segments = |V|``), and one ``max_chunks`` — the max over
    every shard's tight bound (shard_map traces a single kernel grid).
    ``stats`` describe the *global* index, feeding the same cost-model
    decisions (transform/aggregate reordering) as a single-device plan.
    """
    chunk_first: jax.Array   # (num_shards, out_blocks) int32
    chunk_count: jax.Array   # (num_shards, out_blocks) int32
    num_shards: int
    num_rows: int            # E_pad: padded rows per shard
    num_segments: int        # V: the global output space every shard targets
    max_chunks: int          # max over shards' tight bounds, >= 1
    config: KernelConfig
    stats: SegmentStats      # of the global (unpartitioned) index

    def tree_flatten(self):
        children = (self.chunk_first, self.chunk_count)
        aux = (self.num_shards, self.num_rows, self.num_segments,
               self.max_chunks, self.config, self.stats)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def local_plan(self, chunk_first, chunk_count) -> SegmentPlan:
        """The one-shard :class:`SegmentPlan` seen inside ``shard_map``
        (``chunk_first``/``chunk_count``: this shard's (1, out_blocks) or
        (out_blocks,) slices of the stacked leaves)."""
        if chunk_first.ndim == 2:
            chunk_first, chunk_count = chunk_first[0], chunk_count[0]
        return SegmentPlan(chunk_first, chunk_count, self.num_rows,
                           self.num_segments, self.max_chunks, self.config,
                           self.stats)


def make_partitioned_plan(pg, feat: int = 128,
                          config: Optional[KernelConfig] = None,
                          tune: Optional[bool] = None) -> PartitionedPlan:
    """Build one :class:`PartitionedPlan` for a
    :class:`~repro.data.partition.PartitionedGraph`.

    The config is selected once from the per-shard workload (each kernel
    launch reduces ``edges_per_shard`` rows into the global segment space);
    the chunk metadata is evaluated per shard over its padded local dst
    index — padding slots carry ``dst = num_nodes`` and drop out of every
    output window, the same convention :func:`make_plan` uses for row
    padding."""
    dst = np.asarray(pg.dst_global)              # (S, E_pad), pad = V
    valid = np.asarray(pg.edge_valid)
    v = int(pg.num_nodes)
    stats = segment_stats(np.sort(dst[valid]).astype(np.int32), v)

    if config is None:
        from repro.core.heuristics import select_config
        live_per_shard = max(
            max((int(np.unique(dst[s][valid[s]]).size)
                 for s in range(pg.num_shards)), default=0), 1)
        config = select_config(max(int(pg.edges_per_shard), 1),
                               live_per_shard, feat, tune=tune)

    s_b, m_b = config.s_b, config.m_b
    m_pad = _round_up(max(int(pg.edges_per_shard), 1), m_b)
    from repro.kernels.segment_reduce import chunk_metadata
    cf_list, cc_list, max_chunks = [], [], 1
    for s in range(pg.num_shards):
        idxp = np.full((m_pad,), v, np.int32)
        idxp[:dst.shape[1]] = dst[s]
        cf, cc = chunk_metadata(idxp, v, s_b, m_b, m_pad)
        cc_np = np.asarray(cc)
        if cc_np.size:
            max_chunks = max(max_chunks, int(cc_np.max()))
        cf_list.append(np.asarray(cf))
        cc_list.append(cc_np)
    return PartitionedPlan(
        chunk_first=jnp.asarray(np.stack(cf_list)),
        chunk_count=jnp.asarray(np.stack(cc_list)),
        num_shards=int(pg.num_shards),
        num_rows=int(pg.edges_per_shard),
        num_segments=v,
        max_chunks=max_chunks,
        config=config,
        stats=stats,
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class RelationPlan:
    """Precomputed schedule for one grouped-matmul instance (the typed-edge
    analogue of :class:`SegmentPlan`): which relation groups each M_b row
    block of the grouped ``segment_matmul`` grid overlaps, evaluated once
    per typed graph on the host instead of per call at trace time.

    Leaves: ``offsets`` (R+1,), ``first_group`` / ``group_count``
    (int32, (m_blocks,)) — the scalar-prefetch operands of
    :func:`repro.kernels.segment_matmul.segment_matmul_pallas`.
    Aux (static): sizes, the tight ``max_groups`` (max groups any row block
    actually overlaps — the plan-less kernel must assume ``min(R, M_b+1)``),
    the selected ``config``, and :class:`SegmentStats` over the relation
    sizes (skew of the type histogram drives diagnostics and autotuning
    features exactly as degree skew does for the reduces).
    """
    offsets: jax.Array       # (num_groups + 1,) int32 row offsets
    first_group: jax.Array   # (m_blocks,) int32
    group_count: jax.Array   # (m_blocks,) int32
    num_rows: int            # M: rows of X the metadata was built for
    num_groups: int          # R: relation count
    max_groups: int          # tight: max(group_count), >= 1
    config: KernelConfig
    stats: SegmentStats      # over the relation-size histogram

    def tree_flatten(self):
        children = (self.offsets, self.first_group, self.group_count)
        aux = (self.num_rows, self.num_groups, self.max_groups,
               self.config, self.stats)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def worst_case_groups(self) -> int:
        """The group-grid bound the plan-less kernel must assume."""
        return min(self.num_groups, self.config.m_b + 1)

    @property
    def grid_savings(self) -> float:
        """worst-case / tight group-dim ratio (>= 1)."""
        return self.worst_case_groups / max(self.max_groups, 1)

    def validate(self, num_rows: int, num_groups: int) -> None:
        """Trace-time consistency check against the arrays of an op call."""
        if num_rows != self.num_rows or num_groups != self.num_groups:
            raise ValueError(
                f"RelationPlan built for (M={self.num_rows}, "
                f"R={self.num_groups}) used with (M={num_rows}, "
                f"R={num_groups}); rebuild the plan for this typed graph.")


def make_relation_plan(group_sizes, num_rows: Optional[int] = None,
                       feat: int = 128,
                       config: Optional[KernelConfig] = None,
                       tune: Optional[bool] = None) -> RelationPlan:
    """Build a :class:`RelationPlan` from *concrete* per-relation row counts.

    ``group_sizes`` (R,) must be host-available (numpy or committed jax
    array) with non-negative entries; ``num_rows`` defaults to their sum
    (pass the padded row count when X carries trailing out-of-range rows —
    they belong to no group and the metadata drops them, the same
    convention as :func:`make_plan`'s row padding). ``feat`` is the
    representative output width N fed to the config heuristic. ``tune``
    follows the :func:`make_plan` semantics (measured sweep via the
    PerfDB; ``None`` defers to ``REPRO_AUTOTUNE``)."""
    sizes = np.asarray(group_sizes).astype(np.int64)
    if sizes.ndim != 1 or sizes.size == 0:
        raise ValueError(
            f"group_sizes must be 1-D and non-empty, got shape {sizes.shape}")
    if np.any(sizes < 0):
        raise ValueError("group_sizes must be non-negative")
    total = int(sizes.sum())
    m = total if num_rows is None else int(num_rows)
    if m < total:
        raise ValueError(f"num_rows={m} < sum(group_sizes)={total}")
    # the relation-size histogram is a degenerate sorted segment index:
    # reuse the same statistics machinery as the reduces
    stats = segment_stats(np.repeat(np.arange(sizes.size), sizes), sizes.size)

    if config is None:
        from repro.core.heuristics import select_config
        config = select_config(max(m, 1), max(int(sizes.size), 1), feat,
                               op="grouped_segment_matmul", tune=tune)

    # the kernel's own metadata helper, evaluated concretely on the host —
    # one formula, so plans can never drift from the per-call path
    from repro.kernels.segment_matmul import group_metadata
    offsets, fg, gc = group_metadata(sizes.astype(np.int32), m, config.m_b)
    gc_np = np.asarray(gc)
    max_groups = max(1, int(gc_np.max())) if gc_np.size else 1
    return RelationPlan(
        offsets=jnp.asarray(offsets),
        first_group=jnp.asarray(fg),
        group_count=jnp.asarray(gc),
        num_rows=m,
        num_groups=int(sizes.size),
        max_groups=max_groups,
        config=config,
        stats=stats,
    )


def make_graph_plan(edge_index, num_nodes: int, feat: int = 128,
                    config: Optional[KernelConfig] = None,
                    tune: Optional[bool] = None) -> SegmentPlan:
    """Plan for GNN aggregation over ``edge_index`` (2, E) with
    ``edge_index[1]`` (destinations) sorted non-decreasing — the convention
    of :mod:`repro.models.gnn`. One plan serves every layer of a model and
    every training step on the same graph. ``tune=True`` selects the config
    from a measured sweep (see :func:`make_plan`)."""
    edge_index = np.asarray(edge_index)
    if edge_index.ndim != 2 or edge_index.shape[0] != 2:
        raise ValueError(f"edge_index must be (2, E), got {edge_index.shape}")
    return make_plan(edge_index[1], num_nodes, feat=feat, config=config,
                     tune=tune)
