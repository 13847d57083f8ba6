"""Fused plan-aware segment softmax Pallas kernel (GAT attention, §VI).

    out[i, :] = exp(x[i] - m[seg[i]]) / z[seg[i]]
    m[s] = max_{seg[i]==s} x[i],   z[s] = Σ_{seg[i]==s} exp(x[i] - m[s])

replaces the three-pass pure-jnp formulation (segment_max → exp → segment_sum
→ normalize, four HBM round-trips of the (|E|, H) logits) with **one**
launch that consumes the same SegmentPlan chunk metadata as the reduction
kernels.

Schedule: the grid is (out_blocks, 2·max_chunks) — each output block walks
its owned chunk range twice:

  phase 0 (stats) — an SR-style walk with an *online-softmax* accumulator
    (running max m and rescaled sum z: z ← z·e^{m−m'} + e^{x−m'}), flushed
    into (S_b, H) VMEM stat tiles at each segment boundary. One pass gives
    both m and z, numerically stable for arbitrary logit magnitudes.
  phase 1 (emit) — re-walks the same chunks, normalizes each row against its
    segment's stats, and DMAs the finished rows to the per-edge output in
    ANY/HBM memory. Rows are written only by the block owning their segment,
    so shared boundary chunks never clobber a neighbour's rows.

Heads ride the feature (lane) dimension — (E, H) logits are processed as one
lane tile of round_up(H, 128) columns, so multi-head GAT costs the same walk
as single-head. The per-row output DMA has the same sub-512 B granularity
caveat as the fused gather (see ``gather_segment_reduce``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config_space import KernelConfig
from repro.kernels.layout import compiler_params, stream_spec
from repro.kernels.segment_reduce import _resolve_plan, _round_up


def _softmax_body(cf_ref, cc_ref, idx_ref, x_ref, o_ref,
                  m_ref, z_ref, am_ref, az_ref, st_ref, obuf_ref, sem,
                  *, s_b: int, m_b: int, max_chunks: int):
    b, kk = pl.program_id(0), pl.program_id(1)
    k = jax.lax.rem(kk, max_chunks)
    in_stats = kk < max_chunks

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        z_ref[...] = jnp.zeros_like(z_ref)
        st_ref[0] = -1

    @pl.when(jnp.logical_and(in_stats, k < cc_ref[b]))
    def _stats():

        def flush():
            p = st_ref[0]
            m_ref[pl.ds(p, 1), :] = am_ref[...]
            z_ref[pl.ds(p, 1), :] = az_ref[...]

        def walk(i, _):
            r = idx_ref[0, i] - b * s_b
            in_win = jnp.logical_and(r >= 0, r < s_b)
            opened = st_ref[0] >= 0

            @pl.when(jnp.logical_and(opened,
                                     jnp.logical_or(~in_win, r != st_ref[0])))
            def _():
                flush()
                st_ref[0] = -1

            xrow = x_ref[pl.ds(i, 1), :].astype(jnp.float32)

            @pl.when(jnp.logical_and(in_win, st_ref[0] == r))
            def _():  # online-softmax update of the open segment
                new_m = jnp.maximum(am_ref[...], xrow)
                az_ref[...] = (az_ref[...] * jnp.exp(am_ref[...] - new_m)
                               + jnp.exp(xrow - new_m))
                am_ref[...] = new_m

            @pl.when(jnp.logical_and(in_win, st_ref[0] != r))
            def _():  # open a new segment: m = x, z = e^{x-x} = 1
                am_ref[...] = xrow
                az_ref[...] = jnp.ones_like(az_ref)
                st_ref[0] = r

            return 0

        jax.lax.fori_loop(0, m_b, walk, 0, unroll=False)

        @pl.when(jnp.logical_and(k == cc_ref[b] - 1, st_ref[0] >= 0))
        def _():
            flush()
            st_ref[0] = -1

    @pl.when(jnp.logical_and(~in_stats, k < cc_ref[b]))
    def _emit():
        row0 = (cf_ref[b] + k) * m_b

        def row_copy(i):
            # each row is owned by exactly one block's window, and every
            # started copy reads its own obuf row — no slot reuse hazard
            return pltpu.make_async_copy(
                obuf_ref.at[pl.ds(i, 1), :],
                o_ref.at[pl.ds(row0 + i, 1), :],
                sem,
            )

        def compute_and_start(i, _):
            r = idx_ref[0, i] - b * s_b
            in_win = jnp.logical_and(r >= 0, r < s_b)
            rc = jnp.clip(r, 0, s_b - 1)
            xrow = x_ref[pl.ds(i, 1), :].astype(jnp.float32)
            mrow = m_ref[pl.ds(rc, 1), :]
            zrow = z_ref[pl.ds(rc, 1), :]
            obuf_ref[pl.ds(i, 1), :] = (jnp.exp(xrow - mrow)
                                        / jnp.maximum(zrow, 1e-20)
                                        ).astype(obuf_ref.dtype)

            @pl.when(in_win)
            def _():
                row_copy(i).start()

            return 0

        def drain(i, _):
            r = idx_ref[0, i] - b * s_b

            @pl.when(jnp.logical_and(r >= 0, r < s_b))
            def _():
                row_copy(i).wait()

            return 0

        # software-pipelined: all in-window row DMAs are in flight before
        # the first wait (cf. _gather_chunk's overlap in the gather kernel)
        jax.lax.fori_loop(0, m_b, compute_and_start, 0, unroll=False)
        jax.lax.fori_loop(0, m_b, drain, 0, unroll=False)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "config", "max_chunks", "interpret"),
)
def _segment_softmax_impl(x, idx, num_segments: int, config: KernelConfig,
                          max_chunks: Optional[int], interpret: bool,
                          plan=None):
    m, h = x.shape
    s_b, m_b = config.s_b, config.m_b
    h_pad = _round_up(max(h, 1), 128)      # heads ride the lane dimension
    m_pad = _round_up(max(m, 1), m_b)
    s_pad = _round_up(num_segments, s_b)

    # the walk reads and the emit writes single rows, which Mosaic allows
    # only for 32-bit tiles: logits run fp32 through the kernel whatever
    # their io dtype (heads are padded to a 128-lane tile anyway), and the
    # result is cast back to the io dtype
    xp = jnp.pad(x.astype(jnp.float32), ((0, m_pad - m), (0, h_pad - h)))
    idxp = jnp.pad(idx.astype(jnp.int32), (0, m_pad - m),
                   constant_values=num_segments)
    idx3 = idxp.reshape(m_pad // m_b, 1, m_b)

    if plan is not None:
        chunk_first, chunk_count = plan.chunk_first, plan.chunk_count
    else:
        from repro.kernels.segment_reduce import chunk_metadata
        chunk_first, chunk_count = chunk_metadata(idxp, num_segments, s_b,
                                                  m_b, m_pad)
    out_blocks = s_pad // s_b
    if max_chunks is None:
        max_chunks = m_pad // m_b

    def chunk_of(b, kk, cf, cc):
        k = jax.lax.rem(kk, max_chunks)
        return cf[b] + jnp.minimum(k, jnp.maximum(cc[b] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(out_blocks, 2 * max_chunks),
        in_specs=[
            stream_spec(m_b, chunk_of, smem=True),         # seg idx (walk)
            pl.BlockSpec((m_b, h_pad),                     # logits
                         lambda *g: (chunk_of(*g), 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),       # per-edge output
        scratch_shapes=[
            pltpu.VMEM((s_b, h_pad), jnp.float32),         # segment max m
            pltpu.VMEM((s_b, h_pad), jnp.float32),         # segment sum-exp z
            pltpu.VMEM((1, h_pad), jnp.float32),           # open-segment m
            pltpu.VMEM((1, h_pad), jnp.float32),           # open-segment z
            pltpu.SMEM((1,), jnp.int32),                   # open-segment rel
            pltpu.VMEM((m_b, h_pad), jnp.float32),         # output chunk stage
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        functools.partial(_softmax_body, s_b=s_b, m_b=m_b,
                          max_chunks=max_chunks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, h_pad), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="segment_softmax",
    )(chunk_first, chunk_count, idx3, xp)
    out = out[:m, :h]
    # rows of dropped segments (idx >= num_segments, the padding convention
    # of pad_graph / partition) belong to no output block, so no phase-1 DMA
    # ever writes them — the buffer holds garbage there (NaN under the
    # interpreter). Define them as 0: a later weighted aggregation treats α
    # as a per-edge weight, and the PR schedule's one-hot masking multiplies
    # rather than selects, so 0·NaN would poison real outputs.
    out = jnp.where((idx < num_segments)[:, None], out, 0.0)
    return out.astype(x.dtype)


def segment_softmax_pallas(x, idx, num_segments: int,
                           config: Optional[KernelConfig] = None,
                           max_chunks: Optional[int] = None,
                           interpret: bool = False, plan=None):
    """Softmax within sorted segments, (M,) or (M, H) logits, one launch.

    ``plan``: precomputed :class:`repro.core.plan.SegmentPlan` over ``idx``
    (shared with the reduction kernels — same chunk metadata, same tight
    ``max_chunks``).  Only ``s_b``/``m_b`` of the config are consumed (the
    walk is SR-like; heads are a single lane tile)."""
    squeeze = x.ndim == 1
    x2 = x[:, None] if squeeze else x
    config, max_chunks = _resolve_plan(plan, int(idx.shape[0]), num_segments,
                                       config, max_chunks)
    if config is None:
        from repro.core.heuristics import select_config
        config = select_config(int(idx.shape[0]), num_segments,
                               int(x2.shape[1]), op="segment_softmax")
    out = _segment_softmax_impl(x2, idx, num_segments, config, max_chunks,
                                interpret, plan)
    return out[:, 0] if squeeze else out
