"""Fused message+aggregate Pallas kernel (paper §IV, Listing 2):

    Y[s] = reduce_{i: seg[i]==s} (w[i]·) H[gidx[i]]     reduce ∈ {sum, mean, max}

The (|E|, N) message tensor never exists in HBM: each chunk's H rows are
gathered straight into a VMEM staging buffer by per-row async DMA (the TPU
analogue of the fused gather — H stays unblocked in HBM/ANY memory), then the
same PR (MXU one-hot) / SR (VPU walk) reduction as
:mod:`repro.kernels.segment_reduce` consumes the staged tile.

Grid: (feature tiles, T). The second axis walks only the owned (output
block, chunk) steps, block-major and chunk-increasing, through
scalar-prefetched step tables (:func:`repro.kernels.segment_reduce.step_table`);
T = min(⌈E/M_b⌉ + out_blocks − 1, out_blocks × max_chunks) depends on
shapes alone, so a plan pinned to the worst-case ``max_chunks`` walks no
empty steps.

All three reduces are **single-launch** (paper §VI: generalizing the
reduction type does not change the schedule):

  * ``sum``  — the paper's SpMM (weighted) / message-sum (unweighted);
  * ``mean`` — per-segment counts are accumulated inside the same kernel
    (a (S_b, 1) VMEM scratch fed by the one-hot row sums on PR, by a
    per-open-segment counter on SR) and the output block is divided by
    them at its final chunk — no second count launch;
  * ``max``  — SR running-maximum walk with a -inf identity (matching
    ``jax.ops.segment_max`` on empty segments); a PR request falls back to
    SR (a one-hot matmul cannot express max).

Weighted variants reduce over ``w[i]·H[gidx[i]]`` (mean divides by the row
count, matching the reference oracle's "mean of the weighted messages").

Roofline note: each DMA moves one 128-word tile of one row (512 B; the
layout Mosaic accepts for a one-row copy, see :mod:`repro.kernels.layout`),
so a row of F values costs ceil(F·dtype / 512 B) copies, and rows narrower
than a tile still move a whole one.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config_space import KernelConfig
from repro.kernels.layout import (LANES, chunk_stream, compiler_params,
                                  mxu_dot, onehot_t, stream_spec, to_words,
                                  unpack_words, weighted_onehot, word_ratio)
from repro.kernels.segment_reduce import (_resolve_plan, _round_up,
                                          chunk_metadata, flat_grid_steps,
                                          flat_row, flat_step, step_table)


def _gather_chunk(gidx_ref, h_ref, xbuf_ref, sem, j):
    """DMA-gather the chunk's rows of word tile j of H into VMEM staging.

    ``h_ref`` is H as (tiles, V+1, 128) words (layout.to_words), so one row
    of one tile is a contiguous 512-byte copy. Software-pipelined: row
    i+1's copy is issued before waiting on row i, so each DMA's latency
    hides behind the next one's issue."""
    m_b = gidx_ref.shape[1]

    def copy(i):
        return pltpu.make_async_copy(h_ref.at[j, pl.ds(gidx_ref[0, i], 1), :],
                                     xbuf_ref.at[pl.ds(i, 1), :], sem)

    copy(0).start()

    def copy_row(i, carry):
        # issue row i+1 while row i is in flight, then retire row i
        @pl.when(i + 1 < m_b)
        def _():
            copy(i + 1).start()
        copy(i).wait()
        return carry

    jax.lax.fori_loop(0, m_b, copy_row, 0, unroll=False)


def _pr_body(cf_ref, cc_ref, blk_ref, start_ref, gidx_ref, idx_ref, w_ref,
             h_ref, o_ref, xbuf_ref, sem, *scratch, s_b: int,
             has_weight: bool, reduce: str, io_dtype):
    j = pl.program_id(0)
    b, k = flat_step(pl.program_id(1), blk_ref, start_ref)
    cnt_ref = scratch[0] if reduce == "mean" else None

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        if reduce == "mean":
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(k < cc_ref[b])
    def _compute():
        _gather_chunk(gidx_ref, h_ref, xbuf_ref, sem, j)
        # unpacked bf16 values are exact in bf16 again: the MXU runs at the
        # io width and accumulates fp32
        xg = unpack_words(xbuf_ref[...], io_dtype).astype(io_dtype)
        hit = onehot_t(idx_ref, b, s_b)
        a = weighted_onehot(hit, w_ref if has_weight else None, io_dtype)
        o_ref[...] += mxu_dot(a, xg)
        if reduce == "mean":
            # row sums of the one-hot == per-segment row counts. Padding
            # rows carry seg == num_segments: when num_segments % s_b != 0
            # they DO land in the last block's window and count into (and
            # divide) the guard row — correct only because the caller
            # slices the output to [:num_segments].
            cnt_ref[...] += jnp.sum(hit.astype(jnp.float32), axis=1,
                                    keepdims=True)

    if reduce == "mean":
        # normalize once, after the block's last owned chunk accumulated
        @pl.when(k == cc_ref[b] - 1)
        def _normalize():
            o_ref[...] = o_ref[...] / jnp.maximum(cnt_ref[...], 1.0)


def _sr_body(cf_ref, cc_ref, blk_ref, start_ref, gidx_ref, idx_ref, w_ref,
             h_ref, o_ref, xbuf_ref, sem, acc_ref, st_ref, *scratch,
             s_b: int, has_weight: bool, reduce: str, io_dtype):
    j = pl.program_id(0)
    b, k = flat_step(pl.program_id(1), blk_ref, start_ref)
    cnt_ref, ca_ref = scratch if reduce == "mean" else (None, None)
    # max identity is -inf, matching jax.ops.segment_max on empty segments
    init_val = -jnp.inf if reduce == "max" else 0.0

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, init_val)
        st_ref[0] = -1
        if reduce == "mean":
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(k < cc_ref[b])
    def _compute():
        _gather_chunk(gidx_ref, h_ref, xbuf_ref, sem, j)
        m_b = idx_ref.shape[1]

        def flush():
            p = st_ref[0]
            if reduce == "max":
                o_ref[pl.ds(p, 1), :] = jnp.maximum(o_ref[pl.ds(p, 1), :],
                                                    acc_ref[...])
            else:
                o_ref[pl.ds(p, 1), :] += acc_ref[...]
            if reduce == "mean":
                cnt_ref[pl.ds(p, 1), :] += ca_ref[...]

        def walk(i, _):
            r = idx_ref[0, i] - b * s_b
            in_win = jnp.logical_and(r >= 0, r < s_b)
            opened = st_ref[0] >= 0

            @pl.when(jnp.logical_and(opened,
                                     jnp.logical_or(~in_win, r != st_ref[0])))
            def _():
                flush()
                st_ref[0] = -1

            xrow = unpack_words(xbuf_ref[pl.ds(i, 1), :], io_dtype)
            if has_weight:
                xrow = xrow * w_ref[0, i]

            @pl.when(jnp.logical_and(in_win, st_ref[0] == r))
            def _():
                if reduce == "max":
                    acc_ref[...] = jnp.maximum(acc_ref[...], xrow)
                else:
                    acc_ref[...] += xrow
                if reduce == "mean":
                    ca_ref[...] += 1.0

            @pl.when(jnp.logical_and(in_win, st_ref[0] != r))
            def _():
                acc_ref[...] = xrow
                st_ref[0] = r
                if reduce == "mean":
                    ca_ref[...] = jnp.ones_like(ca_ref)

            return 0

        jax.lax.fori_loop(0, m_b, walk, 0, unroll=False)

        @pl.when(jnp.logical_and(k == cc_ref[b] - 1, st_ref[0] >= 0))
        def _():
            flush()
            st_ref[0] = -1

    if reduce == "mean":
        @pl.when(k == cc_ref[b] - 1)
        def _normalize():
            o_ref[...] = o_ref[...] / jnp.maximum(cnt_ref[...], 1.0)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "config", "max_chunks", "interpret",
                     "has_weight", "reduce"),
)
def _gather_segment_reduce_impl(h, gather_idx, seg_idx, weight,
                                num_segments: int, config: KernelConfig,
                                max_chunks: Optional[int], interpret: bool,
                                has_weight: bool, reduce: str = "sum",
                                plan=None):
    m = gather_idx.shape[0]
    v, n = h.shape
    s_b, m_b = config.s_b, config.m_b
    kw = word_ratio(h.dtype)
    m_pad = _round_up(max(m, 1), m_b)
    s_pad = _round_up(num_segments, s_b)

    # H as (tiles, V+1, 128) words, +1 guard row that padding edges gather;
    # the feature tile is one 128-word tile (128·k values) whatever
    # config.n_b says — a wider row is not one DMA on Mosaic
    hw = to_words(jnp.pad(h, ((0, 1), (0, 0))), tiles_first=True)
    idxp = jnp.pad(seg_idx.astype(jnp.int32), (0, m_pad - m),
                   constant_values=num_segments)
    pr = config.schedule == "PR"
    # per-chunk streams: gather rows (DMA addresses) are always SMEM
    # scalars; the PR schedule reads segment ids and weights as VMEM
    # vectors (one-hot build), the SR walk reads them as SMEM scalars —
    # SMEM holds 32-bit words only, so the SR weight stream rides fp32,
    # while PR weights stay in the io dtype (upcast by the MXU's fp32
    # accumulation)
    gidx3 = chunk_stream(gather_idx.astype(jnp.int32), m_pad, m_b, fill=v)
    idx3 = idxp.reshape(m_pad // m_b, 1, m_b)
    w3 = chunk_stream(weight if pr else weight.astype(jnp.float32), m_pad,
                      m_b, fill=0)

    if plan is not None:
        chunk_first, chunk_count = plan.chunk_first, plan.chunk_count
    else:
        chunk_first, chunk_count = chunk_metadata(idxp, num_segments, s_b,
                                                  m_b, m_pad)
    out_blocks = s_pad // s_b
    n_tiles = hw.shape[0]
    if max_chunks is None:
        max_chunks = m_pad // m_b
    n_steps = flat_grid_steps(m_pad // m_b, out_blocks, max_chunks)
    step_block, block_start = step_table(chunk_count, n_steps)

    def row_map(j, t, *tables):
        return flat_row(t, *tables)

    def o_map(j, t, cf, cc, blk, start):
        return (blk[t], j)

    # tile-major, then the flat (block, chunk) steps: each output block
    # (b, j) is visited in one run of consecutive steps
    common = dict(
        grid=(n_tiles, n_steps),
        in_specs=[
            stream_spec(m_b, row_map, smem=True),             # gather_idx
            stream_spec(m_b, row_map, smem=not pr),           # seg_idx
            stream_spec(m_b, row_map, smem=not pr),           # weight
            pl.BlockSpec(memory_space=pl.ANY),                # H (unblocked)
        ],
        out_specs=pl.BlockSpec((s_b, LANES * kw), o_map),
    )
    scratch = [pltpu.VMEM((m_b, LANES), hw.dtype), pltpu.SemaphoreType.DMA]
    # fused mean: per-segment row counts live next to the output block
    cnt_scratch = [pltpu.VMEM((s_b, 1), jnp.float32)] if reduce == "mean" else []

    if pr:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, **common,
            scratch_shapes=scratch + cnt_scratch)
        body = functools.partial(_pr_body, s_b=s_b, has_weight=has_weight,
                                 reduce=reduce, io_dtype=h.dtype)
    else:
        sr_scratch = [pltpu.VMEM((1, LANES * kw), jnp.float32),
                      pltpu.SMEM((1,), jnp.int32)]
        if reduce == "mean":
            sr_scratch += cnt_scratch + [pltpu.VMEM((1, 1), jnp.float32)]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, **common, scratch_shapes=scratch + sr_scratch)
        body = functools.partial(_sr_body, s_b=s_b, has_weight=has_weight,
                                 reduce=reduce, io_dtype=h.dtype)

    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_pad, n_tiles * LANES * kw),
                                        jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="gather_segment_reduce",
    )(chunk_first, chunk_count, step_block, block_start, gidx3, idx3, w3, hw)

    return out[:num_segments, :n].astype(h.dtype)


def gather_segment_reduce_pallas(h, gather_idx, seg_idx, num_segments: int,
                                 weight=None, reduce: str = "sum",
                                 config: Optional[KernelConfig] = None,
                                 max_chunks: Optional[int] = None,
                                 interpret: bool = False, plan=None):
    """Fused Y[s] = reduce_{seg[i]==s} (w[i]·) H[gather_idx[i]] — one launch
    for every reduce ∈ {sum, mean, max} (format-agnostic SpMM when sum +
    weighted).  seg_idx must be sorted non-decreasing. ``plan``: precomputed
    :class:`repro.core.plan.SegmentPlan` over ``seg_idx`` (shared with the
    unfused kernel — both consume the same chunk metadata)."""
    if reduce not in ("sum", "mean", "max"):
        raise ValueError(f"unknown reduce: {reduce!r}")
    config, max_chunks = _resolve_plan(plan, int(gather_idx.shape[0]),
                                       num_segments, config, max_chunks)
    if config is None:
        from repro.core.heuristics import select_config
        config = select_config(int(gather_idx.shape[0]), num_segments,
                               int(h.shape[1]))
    if reduce == "max" and config.schedule == "PR":
        # a one-hot matmul cannot express max; same tiling, SR walk instead
        config = KernelConfig("SR", config.s_b, config.n_b, config.m_b, 1)
    has_weight = weight is not None
    if weight is None:
        # dummy ones ride the io dtype so the unused stream stays narrow
        weight = jnp.ones((gather_idx.shape[0],), h.dtype)
    return _gather_segment_reduce_impl(h, gather_idx, seg_idx, weight,
                                       num_segments, config, max_chunks,
                                       interpret, has_weight, reduce, plan)
