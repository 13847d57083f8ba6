"""Fully-fused transform-aggregate Pallas kernel — SpMM+GEMM in one launch.

    Y[s, :] = ( reduce_{i: seg[i]==s} wt[i] · H[gidx[i], :] ) @ W

The step beyond ``mp_transform``'s reorder-only fusion: the per-layer dense
transform runs *inside* the gather-reduce launch, so neither the transformed
(|E|, d) edge tensor (transform-first) nor the aggregated (S, d_in) node
tensor (aggregate-first) ever exists in HBM. Linear reduces only
(sum / mean) — the transform distributes over the reduction, which is what
makes aggregating at width d_in and transforming per output block
mathematically identical to transform-then-aggregate.

Schedule (grid = (T,), the gather kernel's flat walk of the owned
(output block, chunk) steps; **no feature tiling**):

  * each chunk's H rows are DMA-gathered at full d_in width into VMEM
    staging — one copy per row instead of the ``n_tiles`` copies the
    width-tiled gather kernel issues, because the in-kernel GEMM needs the
    whole contraction dim resident anyway;
  * the PR one-hot matmul accumulates the chunk into an (S_b, d_in) fp32
    VMEM accumulator (same masking convention as ``gather_segment_reduce``);
  * at the block's last step the accumulator (mean-normalized if
    requested) hits the MXU against the VMEM-resident (d_in, d_out) weight
    tile and the (S_b, d_out) result is written out in the io dtype.

VMEM feasibility: W + accumulator + staging must fit (checked by
:func:`fusable`); past that bound callers fall back to the two-launch
``mp_transform`` path — ``core.mp.resolve_order`` consults the same
predicate.

Precision: io dtype in (H, W, wt, Y out), fp32 accumulate — the segment
accumulator is always fp32 and both matmuls run with
``preferred_element_type=float32``; for bf16 io the accumulator is cast to
bf16 once, immediately before the transform matmul (the MXU's native
operand width).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config_space import VMEM_BYTES, KernelConfig, io_dtype_bytes
from repro.kernels.gather_segment_reduce import _gather_chunk
from repro.kernels.layout import (LANES, chunk_stream, compiler_params,
                                  mxu_dot, onehot_t, stream_spec, to_words,
                                  unpack_words, weighted_onehot, word_cols)
from repro.kernels.segment_reduce import (_resolve_plan, _round_up,
                                          chunk_metadata, flat_grid_steps,
                                          flat_row, flat_step, step_table)


def fusable(d_in: int, d_out: int, dtype, config: KernelConfig,
            budget: int = VMEM_BYTES) -> bool:
    """Does one launch's VMEM working set fit? The W tile and the out block
    are pipelined operands (two buffers each); the fp32 accumulator and the
    staged chunk of words are single scratch buffers."""
    b = io_dtype_bytes(dtype)
    d_in_pad = word_cols(d_in, dtype)
    d_out_pad = _round_up(max(d_out, 1), 128)
    w_tile = 2 * d_in_pad * d_out_pad * b
    acc = config.s_b * d_in_pad * 4
    stage = config.m_b * d_in_pad * b
    out = 2 * config.s_b * d_out_pad * b
    return w_tile + acc + stage + out <= budget


def _body(cf_ref, cc_ref, blk_ref, start_ref, gidx_ref, idx_ref, wt_ref,
          h_ref, wm_ref, o_ref, xbuf_ref, acc_ref, sem, *scratch, s_b: int,
          has_weight: bool, reduce: str, io_dtype):
    b, k = flat_step(pl.program_id(0), blk_ref, start_ref)
    cnt_ref = scratch[0] if reduce == "mean" else None

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if reduce == "mean":
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(k < cc_ref[b])
    def _accumulate():
        hit = onehot_t(idx_ref, b, s_b)
        a = weighted_onehot(hit, wt_ref if has_weight else None, io_dtype)
        cols = acc_ref.shape[1] // xbuf_ref.shape[0]
        for t in range(xbuf_ref.shape[0]):          # whole rows, tile by tile
            _gather_chunk(gidx_ref, h_ref, xbuf_ref.at[t], sem, t)
            xg = unpack_words(xbuf_ref[t], io_dtype).astype(io_dtype)
            acc_ref[:, t * cols:(t + 1) * cols] += mxu_dot(a, xg)
        if reduce == "mean":
            # one-hot row sums == per-segment row counts (padding rows
            # carry seg == num_segments and only ever land in the guard
            # rows the caller slices away — same convention as the gather
            # kernel's fused mean)
            cnt_ref[...] += jnp.sum(hit.astype(jnp.float32), axis=1,
                                    keepdims=True)

    # in-kernel GEMM once per output block, after its last owned chunk
    # (blocks owning no chunks fire on their one step with a zero
    # accumulator)
    @pl.when(k == jnp.maximum(cc_ref[b], 1) - 1)
    def _transform():
        agg = acc_ref[...]
        if reduce == "mean":
            agg = agg / jnp.maximum(cnt_ref[...], 1.0)
        o_ref[...] = mxu_dot(agg.astype(wm_ref.dtype),
                             wm_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "config", "max_chunks", "interpret",
                     "has_weight", "reduce"),
)
def _fused_transform_reduce_impl(h, wm, gather_idx, seg_idx, weight,
                                 num_segments: int, config: KernelConfig,
                                 max_chunks: Optional[int], interpret: bool,
                                 has_weight: bool, reduce: str, plan=None):
    m = gather_idx.shape[0]
    v, d_in = h.shape
    d_out = wm.shape[1]
    s_b, m_b = config.s_b, config.m_b
    d_in_pad = word_cols(d_in, h.dtype)
    d_out_pad = _round_up(max(d_out, 1), 128)
    m_pad = _round_up(max(m, 1), m_b)
    s_pad = _round_up(num_segments, s_b)

    # H travels as (tiles, V+1, 128) words with a +1 guard row (see the
    # gather kernel)
    hw = to_words(jnp.pad(h, ((0, 1), (0, 0))), tiles_first=True)
    wmp = jnp.pad(wm, ((0, d_in_pad - d_in), (0, d_out_pad - d_out)))
    gidxp = jnp.pad(gather_idx.astype(jnp.int32), (0, m_pad - m),
                    constant_values=v)               # padding gathers guard row
    idxp = jnp.pad(seg_idx.astype(jnp.int32), (0, m_pad - m),
                   constant_values=num_segments)
    # streams as in the gather kernel's PR schedule: gather rows are SMEM
    # scalars, segment ids and io-dtype weights VMEM vectors
    gidx3 = gidxp.reshape(m_pad // m_b, 1, m_b)
    idx3 = idxp.reshape(m_pad // m_b, 1, m_b)
    wt3 = chunk_stream(weight, m_pad, m_b, fill=0)

    if plan is not None:
        chunk_first, chunk_count = plan.chunk_first, plan.chunk_count
    else:
        chunk_first, chunk_count = chunk_metadata(idxp, num_segments, s_b,
                                                  m_b, m_pad)
    out_blocks = s_pad // s_b
    if max_chunks is None:
        max_chunks = m_pad // m_b
    n_steps = flat_grid_steps(m_pad // m_b, out_blocks, max_chunks)
    step_block, block_start = step_table(chunk_count, n_steps)

    def row_map(t, *tables):
        return flat_row(t, *tables)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_steps,),
        in_specs=[
            stream_spec(m_b, row_map, smem=True),              # gather_idx
            stream_spec(m_b, row_map, smem=False),             # seg_idx
            stream_spec(m_b, row_map, smem=False),             # edge weight
            pl.BlockSpec(memory_space=pl.ANY),                 # H (unblocked)
            pl.BlockSpec((d_in_pad, d_out_pad), lambda t, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((s_b, d_out_pad),
                               lambda t, cf, cc, blk, start: (blk[t], 0)),
        scratch_shapes=(
            [pltpu.VMEM((hw.shape[0], m_b, LANES), hw.dtype),  # staged rows
             pltpu.VMEM((s_b, d_in_pad), jnp.float32),         # fp32 segment acc
             pltpu.SemaphoreType.DMA]
            + ([pltpu.VMEM((s_b, 1), jnp.float32)]             # mean counts
               if reduce == "mean" else [])),
    )
    out = pl.pallas_call(
        functools.partial(_body, s_b=s_b, has_weight=has_weight,
                          reduce=reduce, io_dtype=h.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_pad, d_out_pad), h.dtype),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="fused_transform_reduce",
    )(chunk_first, chunk_count, step_block, block_start, gidx3, idx3, wt3,
      hw, wmp)
    return out[:num_segments, :d_out]


def fused_transform_reduce_pallas(h, w, gather_idx, seg_idx,
                                  num_segments: int, weight=None,
                                  reduce: str = "sum",
                                  config: Optional[KernelConfig] = None,
                                  max_chunks: Optional[int] = None,
                                  interpret: bool = False, plan=None):
    """One-launch Y = Agg(H)[gather/seg] @ W for reduce ∈ {sum, mean}
    (weighted or not). ``seg_idx`` must be sorted non-decreasing; ``plan``
    is the same :class:`~repro.core.plan.SegmentPlan` the gather-reduce
    kernels consume (identical chunk metadata)."""
    if reduce not in ("sum", "mean"):
        raise ValueError(f"fused transform-reduce is linear-only: "
                         f"reduce must be sum or mean, got {reduce!r}")
    config, max_chunks = _resolve_plan(plan, int(gather_idx.shape[0]),
                                       num_segments, config, max_chunks)
    if config is None:
        from repro.core.config_space import canonical_io_dtype
        from repro.core.heuristics import select_config
        config = select_config(int(gather_idx.shape[0]), num_segments,
                               int(h.shape[1]), op="fused_transform_reduce",
                               io_dtype=canonical_io_dtype(h.dtype))
    if not fusable(int(h.shape[1]), int(w.shape[1]), h.dtype, config):
        raise ValueError(
            f"(d_in={h.shape[1]}, d_out={w.shape[1]}) exceeds the fused "
            f"kernel's VMEM budget for config {config}; use the two-launch "
            f"mp_transform path (core.mp.resolve_order gates on "
            f"kernels.fused_transform_reduce.fusable)")
    has_weight = weight is not None
    if weight is None:
        weight = jnp.ones((gather_idx.shape[0],), h.dtype)
    return _fused_transform_reduce_impl(h, w, gather_idx, seg_idx, weight,
                                        num_segments, config, max_chunks,
                                        interpret, has_weight, reduce, plan)
