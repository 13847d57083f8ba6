"""Pallas TPU segment-reduction kernel (paper §III adapted to TPU).

Schedules (DESIGN.md §2):
  PR — "parallel reduction": per chunk, a one-hot matrix P (M_b × S_b) is
       built on the VPU and `out += Pᵀ @ X` runs on the MXU. The systolic
       array performs the cross-row reduction that warp shuffles perform on
       GPU; rows whose segment falls outside the output window produce
       all-zero P rows (the analogue of shuffle invalidation).
  SR — "sequential reduction": a scalar walk down the chunk with a (1, N_b)
       vector accumulator, flushing to the output block row at each segment
       boundary (dynamic-slice store). Sequential in M, vectorized in N.

Grid & tiling:
  grid = (out_blocks, n_tiles, max_chunks)   — chunk dim innermost.
  Each output block b owns segment ids [b·S_b, (b+1)·S_b). Because Idx is
  sorted, the input rows feeding block b form a contiguous range; the
  scalar-prefetched metadata (chunk_first, chunk_count) maps b to its chunk
  range. Chunks shared with a neighbouring block are re-read by both; the
  one-hot / window test masks out the foreign rows, so no atomics are needed
  (TPU grid steps are sequential — the structural replacement for
  atomicAdd, see DESIGN.md §2).

No shared-memory-style staging between "thread groups" is used, matching the
paper's design decision (§III-A).

Note on K_c (the G_t analogue): it parameterises the MXU contraction depth
per one-hot sub-matmul in the *cost model* (pipeline-fill efficiency,
repro.core.costmodel). Mosaic schedules the systolic pipeline internally, so
the kernel body issues the full-chunk dot and K_c is a model-level knob; on
GPU G_t is a launch parameter, on TPU its twin lives in the scheduler.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config_space import KernelConfig
from repro.kernels.layout import (LANES, compiler_params, mxu_dot, onehot_t,
                                  stream_spec, to_words, unpack_words,
                                  weighted_onehot, word_cols, word_ratio)


# ---------------------------------------------------------------------------
# metadata (jit-safe; only `max_chunks` must be static)
# ---------------------------------------------------------------------------

def chunk_metadata(idx, num_segments: int, s_b: int, m_b: int, m_pad: int):
    """Per-output-block chunk range over the padded row space.

    Returns (chunk_first, chunk_count) of shape (out_blocks,): block b reads
    input-row blocks [chunk_first[b], chunk_first[b] + chunk_count[b])."""
    out_blocks = (num_segments + s_b - 1) // s_b
    bounds = jnp.arange(out_blocks + 1, dtype=jnp.int32) * s_b
    # row range [lo_b, hi_b) of segment ids < bound — sorted Idx ⇒ searchsorted
    row_bound = jnp.searchsorted(idx, bounds, side="left").astype(jnp.int32)
    lo, hi = row_bound[:-1], row_bound[1:]
    chunk_first = lo // m_b
    last = jnp.maximum(hi - 1, lo) // m_b
    chunk_count = jnp.where(hi > lo, last - chunk_first + 1, 0).astype(jnp.int32)
    return chunk_first, chunk_count


# ---------------------------------------------------------------------------
# flat grid over the owned (block, chunk) steps (jit-safe; length static)
# ---------------------------------------------------------------------------

def flat_grid_steps(n_chunks: int, out_blocks: int, max_chunks: int) -> int:
    """Static length T of the flat grid over ``n_chunks = m_pad // m_b``
    input chunks. Sorted ids make consecutive blocks' chunk ranges overlap
    in at most one chunk, so the owned steps, plus one for each block that
    owns none, number at most ``n_chunks + out_blocks - 1``; the ``min``
    keeps a tight plan from walking more than its (out_blocks, max_chunks)
    grid would."""
    return min(n_chunks + out_blocks - 1, out_blocks * max_chunks)


def step_table(chunk_count, n_steps: int):
    """Block-major tables of the flat grid, from the plan's chunk counts.

    Block b takes ``max(chunk_count[b], 1)`` consecutive steps from
    ``block_start[b]`` on (one for a block that owns nothing: its init, or
    the fused kernel's transform). Returns ``(step_block, block_start)``
    of shapes (n_steps,) and (out_blocks,): steps past the last block's
    are inert, mapped to the last block beyond its last chunk."""
    steps = jnp.maximum(chunk_count, 1)
    block_start = (jnp.cumsum(steps) - steps).astype(jnp.int32)
    step_block = jnp.searchsorted(block_start,
                                  jnp.arange(n_steps, dtype=jnp.int32),
                                  side="right") - 1
    return step_block.astype(jnp.int32), block_start


def flat_step(t, step_block, block_start):
    """(output block, its chunk ordinal k) of flat grid step ``t``."""
    b = step_block[t]
    return b, t - block_start[b]


def flat_row(t, cf, cc, step_block, block_start):
    """Input chunk that flat step ``t`` reads: its block's k-th, held at
    the block's last (a block owning none reads its first; an inert step
    re-reads the last block's last chunk, so it fetches nothing new)."""
    b, k = flat_step(t, step_block, block_start)
    return cf[b] + jnp.minimum(k, jnp.maximum(cc[b] - 1, 0))


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _pr_body(cf_ref, cc_ref, idx_ref, x_ref, o_ref, *, s_b: int, io_dtype):
    b, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(k < cc_ref[b])
    def _compute():
        # unpacked bf16 values are exact in bf16 again: the MXU runs at the
        # io width and accumulates fp32
        x = unpack_words(x_ref[...], io_dtype).astype(io_dtype)
        a = weighted_onehot(onehot_t(idx_ref, b, s_b), None, io_dtype)
        o_ref[...] += mxu_dot(a, x)


def _sr_body(cf_ref, cc_ref, idx_ref, x_ref, o_ref, acc_ref, st_ref,
             *, s_b: int, reduce: str, io_dtype):
    b, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    # max identity is -inf, matching jax.ops.segment_max on empty segments
    init_val = -jnp.inf if reduce == "max" else 0.0

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, init_val)
        st_ref[0] = -1                                # open-segment rel (-1 ⇒ closed)

    @pl.when(k < cc_ref[b])
    def _compute():
        m_b = idx_ref.shape[1]

        def flush():
            p = st_ref[0]
            row = o_ref[pl.ds(p, 1), :]
            if reduce == "max":
                o_ref[pl.ds(p, 1), :] = jnp.maximum(row, acc_ref[...])
            else:
                o_ref[pl.ds(p, 1), :] = row + acc_ref[...]

        def walk(i, _):
            r = idx_ref[0, i] - b * s_b
            in_win = jnp.logical_and(r >= 0, r < s_b)
            opened = st_ref[0] >= 0

            # segment boundary (or leaving the window) ⇒ flush accumulator
            @pl.when(jnp.logical_and(opened, jnp.logical_or(~in_win, r != st_ref[0])))
            def _():
                flush()
                st_ref[0] = -1

            xrow = unpack_words(x_ref[pl.ds(i, 1), :], io_dtype)

            @pl.when(jnp.logical_and(in_win, st_ref[0] == r))
            def _():  # continue open segment
                if reduce == "max":
                    acc_ref[...] = jnp.maximum(acc_ref[...], xrow)
                else:
                    acc_ref[...] += xrow

            @pl.when(jnp.logical_and(in_win, st_ref[0] != r))
            def _():  # open a new segment
                acc_ref[...] = xrow
                st_ref[0] = r

            return 0

        jax.lax.fori_loop(0, m_b, walk, 0, unroll=False)

        # end of this block's chunk range ⇒ flush the trailing open segment
        @pl.when(jnp.logical_and(k == cc_ref[b] - 1, st_ref[0] >= 0))
        def _():
            flush()
            st_ref[0] = -1


# ---------------------------------------------------------------------------
# pallas_call wrapper
# ---------------------------------------------------------------------------

def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _resolve_plan(plan, num_rows: int, num_segments: int,
                  config: Optional[KernelConfig],
                  max_chunks: Optional[int]):
    """Merge an optional SegmentPlan into (config, max_chunks).

    The plan's config wins when none is given explicitly; an explicit config
    must agree on the tiling the metadata was built for (s_b, m_b)."""
    if plan is None:
        return config, max_chunks
    plan.validate(num_rows, num_segments)
    if config is None:
        config = plan.config
    elif (config.s_b, config.m_b) != (plan.config.s_b, plan.config.m_b):
        raise ValueError(
            f"explicit config (s_b={config.s_b}, m_b={config.m_b}) conflicts "
            f"with plan tiling (s_b={plan.config.s_b}, m_b={plan.config.m_b})")
    if max_chunks is None:
        max_chunks = plan.max_chunks
    return config, max_chunks


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "reduce", "config", "max_chunks",
                     "interpret"),
)
def segment_reduce_pallas(x, idx, num_segments: int, reduce: str = "sum",
                          config: Optional[KernelConfig] = None,
                          max_chunks: Optional[int] = None,
                          interpret: bool = False, plan=None):
    """Blocked segment reduction via pl.pallas_call.

    x: (M, N); idx: (M,) sorted int32; returns (num_segments, N) in x.dtype.
    ``max_chunks``: static bound on chunks per output block (worst case:
    all rows in one block). Tighten it for skewed inputs when known.
    ``plan``: a precomputed :class:`repro.core.plan.SegmentPlan` — supplies
    config, a tight ``max_chunks``, and the chunk metadata, skipping their
    per-call recomputation.
    """
    config, max_chunks = _resolve_plan(plan, int(x.shape[0]), num_segments,
                                       config, max_chunks)
    if config is None:
        from repro.core.heuristics import select_config
        config = select_config(int(x.shape[0]), num_segments, int(x.shape[1]))
    if reduce == "max" and config.schedule == "PR":
        config = KernelConfig("SR", config.s_b, config.n_b, config.m_b, 1)
    if reduce == "mean":
        s = segment_reduce_pallas(x, idx, num_segments, "sum", config,
                                  max_chunks, interpret, plan)
        cnt = jax.ops.segment_sum(jnp.ones((x.shape[0],), jnp.float32), idx,
                                  num_segments, indices_are_sorted=True)
        return (s.astype(jnp.float32)
                / jnp.maximum(cnt, 1.0)[:, None]).astype(x.dtype)

    m, n = x.shape
    s_b, m_b = config.s_b, config.m_b
    kw = word_ratio(x.dtype)
    # x travels as 32-bit words (layout.to_words): the SR walk reads single
    # rows, which Mosaic allows only for 32-bit tiles. A feature tile is
    # n_b values = n_b / k words, at least one 128-word tile.
    words = word_cols(n, x.dtype) // kw
    nbw = min(max(config.n_b // kw // LANES, 1) * LANES, words)
    w_pad = _round_up(words, nbw)
    m_pad = _round_up(max(m, 1), m_b)
    s_pad = _round_up(num_segments, s_b)

    xw = to_words(x, tiles_first=False)
    xw = jnp.pad(xw, ((0, m_pad - m), (0, w_pad - xw.shape[1])))
    # padding rows get segment id = num_segments ⇒ outside every window
    idxp = jnp.pad(idx.astype(jnp.int32), (0, m_pad - m),
                   constant_values=num_segments)
    pr = config.schedule == "PR"
    idx3 = idxp.reshape(m_pad // m_b, 1, m_b)

    if plan is not None:
        chunk_first, chunk_count = plan.chunk_first, plan.chunk_count
    else:
        chunk_first, chunk_count = chunk_metadata(idxp, num_segments, s_b,
                                                  m_b, m_pad)
    out_blocks = s_pad // s_b
    n_tiles = w_pad // nbw
    if max_chunks is None:
        max_chunks = m_pad // m_b          # worst case: one block owns all rows

    def chunk_of(b, j, k, cf, cc):
        return cf[b] + jnp.minimum(k, jnp.maximum(cc[b] - 1, 0))

    def x_map(b, j, k, cf, cc):
        return (chunk_of(b, j, k, cf, cc), j)

    def o_map(b, j, k, cf, cc):
        return (b, j)

    common = dict(
        grid=(out_blocks, n_tiles, max_chunks),
        in_specs=[
            stream_spec(m_b, chunk_of, smem=not pr),  # PR: vector, SR: scalars
            pl.BlockSpec((m_b, nbw), x_map),
        ],
        out_specs=pl.BlockSpec((s_b, nbw * kw), o_map),
    )

    if pr:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, **common)
        body = functools.partial(_pr_body, s_b=s_b, io_dtype=x.dtype)
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, **common,
            scratch_shapes=[pltpu.VMEM((1, nbw * kw), jnp.float32),
                            pltpu.SMEM((1,), jnp.int32)])
        body = functools.partial(_sr_body, s_b=s_b, reduce=reduce,
                                 io_dtype=x.dtype)

    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_pad, w_pad * kw), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="segment_reduce",
    )(chunk_first, chunk_count, idx3, xw)

    # empty max segments keep the -inf identity, as jax.ops.segment_max does
    return out[:num_segments, :n].astype(x.dtype)
