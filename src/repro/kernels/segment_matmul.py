"""Grouped (segment) matmul Pallas kernel — GeoT-extension op.

    out[rows of group e, :] = X[rows of group e, :] @ W[e]

with X (M, K) sorted so each group's rows are contiguous (the MoE expert FFN
hot path: tokens sorted by expert id; the heterogeneous-GNN hot path:
edge messages sorted by relation type — FASTEN's critical operator).  Same
sortedness contract as segment reduction.  Oracle: ``jax.lax.ragged_dot``.

Tiling: grid = (m_blocks, n_tiles, max_groups_per_block).  A row block of
M_b rows usually lies inside one group (MoE segments ≫ M_b); boundary blocks
overlap ≤ max_groups groups, enumerated by the innermost grid dim with rows
outside the current group masked to zero *before* the MXU matmul.  The
output block accumulates across the group dim (sequential grid ⇒ race-free).

The per-block group metadata (first group / group count per row block, and
the tight ``max_groups`` bound) is exactly what a
:class:`~repro.core.plan.RelationPlan` precomputes once per typed graph —
:func:`group_metadata` is the single formula both paths evaluate, so plans
can never drift from the per-call computation (the same one-formula
guarantee :func:`repro.kernels.segment_reduce.chunk_metadata` gives
:class:`~repro.core.plan.SegmentPlan`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_reduce import _round_up


def _body(off_ref, fg_ref, gc_ref, x_ref, w_ref, o_ref, *, m_b: int):
    mb, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(k < gc_ref[mb])
    def _compute():
        g = fg_ref[mb] + k
        rows = mb * m_b + jax.lax.broadcasted_iota(jnp.int32, (m_b, 1), 0)
        mask = jnp.logical_and(rows >= off_ref[g], rows < off_ref[g + 1])
        xm = jnp.where(mask, x_ref[...], jnp.zeros((), x_ref.dtype))
        # fp32 MXU accumulate, io-dtype store: each output row is owned by
        # exactly one group (foreign rows are masked to zero before the
        # matmul), so the += across the group grid dim only ever adds zeros
        # to already-written rows — storing in the io dtype loses nothing.
        o_ref[...] += jax.lax.dot_general(
            xm, w_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def group_metadata(group_sizes, num_rows: int, m_b: int):
    """Per-row-block group schedule for the grouped matmul grid.

    Returns ``(offsets, first_group, group_count)``:

      * ``offsets`` (E+1,) — cumulative row offsets per group;
      * ``first_group`` (m_blocks,) — the group owning each block's first
        live row;
      * ``group_count`` (m_blocks,) — how many groups the block overlaps
        (0 for blocks made purely of padding rows).

    One formula for both the per-call trace-time path (jnp on traced
    arrays) and the host-side :class:`~repro.core.plan.RelationPlan`
    construction (jnp on concrete numpy — evaluated eagerly)."""
    group_sizes = jnp.asarray(group_sizes)
    e = group_sizes.shape[0]
    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(group_sizes.astype(jnp.int32))])
    m_pad = _round_up(max(num_rows, 1), m_b)
    m_blocks = m_pad // m_b
    starts = jnp.arange(m_blocks, dtype=jnp.int32) * m_b
    ends = starts + (m_b - 1)
    # group containing a row r: searchsorted(offsets, r, 'right') - 1
    fg = jnp.clip(jnp.searchsorted(offsets, starts, side="right") - 1,
                  0, e - 1)
    lg = jnp.clip(jnp.searchsorted(offsets,
                                   jnp.minimum(ends, num_rows - 1),
                                   side="right") - 1, 0, e - 1)
    gc = (lg - fg + 1).astype(jnp.int32)
    # blocks made purely of padding rows do no work
    gc = jnp.where(starts >= num_rows, 0, gc).astype(jnp.int32)
    return offsets, fg.astype(jnp.int32), gc


@functools.partial(jax.jit,
                   static_argnames=("m_b", "n_b", "max_groups", "interpret"))
def segment_matmul_pallas(x, group_sizes, w, m_b: int = 128,
                          n_b: int = 128, max_groups: Optional[int] = None,
                          interpret: bool = False, offsets=None,
                          first_group=None, group_count=None):
    """x: (M, K) group-sorted; group_sizes: (E,) with sum ≤ M; w: (E, K, N).

    ``offsets``/``first_group``/``group_count``: precomputed
    :func:`group_metadata` (a RelationPlan's leaves) — when given, the
    per-call searchsorted is skipped entirely; pair them with the plan's
    tight ``max_groups`` so the grid's group dimension is O(actual
    boundary overlap) instead of O(min(E, M_b+1))."""
    m, kdim = x.shape
    e, _, n = w.shape
    n_b = min(n_b, _round_up(max(n, 1), 128))
    m_pad = _round_up(max(m, 1), m_b)
    n_pad = _round_up(max(n, 1), n_b)

    xp = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, n_pad - n)))

    if offsets is None:
        offsets, first_group, group_count = group_metadata(group_sizes, m,
                                                           m_b)
    fg, gc = first_group, group_count

    if max_groups is None:
        max_groups = min(e, m_b + 1)
    m_blocks = m_pad // m_b
    n_tiles = n_pad // n_b

    def x_map(mb, j, k, off, fg_, gc_):
        return (mb, 0)

    def w_map(mb, j, k, off, fg_, gc_):
        return (fg_[mb] + jnp.minimum(k, jnp.maximum(gc_[mb] - 1, 0)), 0, j)

    def o_map(mb, j, k, off, fg_, gc_):
        return (mb, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(m_blocks, n_tiles, max_groups),
        in_specs=[
            pl.BlockSpec((m_b, kdim), x_map),
            pl.BlockSpec((1, kdim, n_b), w_map),
        ],
        out_specs=pl.BlockSpec((m_b, n_b), o_map),
    )

    # out buffer in the io dtype: bf16 grouped matmuls must not materialize
    # a 2x-size fp32 intermediate (the MXU still accumulates fp32 per tile
    # via preferred_element_type in the kernel body).
    out = pl.pallas_call(
        functools.partial(_body, m_b=m_b),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), x.dtype),
        interpret=interpret,
        name="segment_matmul",
    )(offsets, fg, gc, xp, wp)
    return out[:m, :n]
