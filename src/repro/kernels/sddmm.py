"""SDDMM Pallas kernel — sampled dense-dense matmul (paper §VI).

    out[i] = < A[row_idx[i], :] , B[col_idx[i], :] >      i ∈ [0, M)

SDDMM is the backward of the fused SpMM (`index_weight_segment_reduce`'s
dW) — the op the paper names as the missing piece for training support.
TPU mapping: grid over edge chunks; both operand rows are DMA-gathered into
VMEM staging buffers (same per-row async-copy machinery as
gather_segment_reduce), then the per-edge dot is an elementwise multiply +
lane reduction on the VPU. No sortedness required (pure gather, no scatter).

Precision contract: **fp32-accumulate / input-dtype-out.** The per-edge dot
multiplies in fp32 and the feature-tile partials accumulate across the
sequential ``j`` grid dim in an fp32 output buffer (a real running sum —
unlike the grouped matmul's masked-disjoint accumulation it cannot be
narrowed); the (M,) result is cast to ``a.dtype`` on the way out, so bf16
operands get bf16 edge scores without ever accumulating in bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import (LANES, chunk_stream, compiler_params,
                                  stream_spec, to_words, unpack_words)
from repro.kernels.segment_reduce import _round_up


def _body(ridx_ref, cidx_ref, a_ref, b_ref, o_ref, abuf_ref, bbuf_ref, sem,
          *, io_dtype):
    m_b = ridx_ref.shape[1]
    j = pl.program_id(1)

    def copy_row(i, _):
        # one 128-word tile of one row per copy (layout.to_words)
        for src, idx_ref, buf in ((a_ref, ridx_ref, abuf_ref),
                                  (b_ref, cidx_ref, bbuf_ref)):
            cp = pltpu.make_async_copy(src.at[j, pl.ds(idx_ref[0, i], 1), :],
                                       buf.at[pl.ds(i, 1), :], sem)
            cp.start()
            cp.wait()
        return 0

    jax.lax.fori_loop(0, m_b, copy_row, 0, unroll=False)
    prod = (unpack_words(abuf_ref[...], io_dtype)
            * unpack_words(bbuf_ref[...], io_dtype))
    # edges along lanes: a (1, M_b) row of per-edge partial dots, matching
    # the output block's layout without a sublane→lane relayout
    partial = jnp.sum(prod.T, axis=0, keepdims=True)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial       # accumulate feature tiles (j sequential)


@functools.partial(jax.jit, static_argnames=("m_b", "interpret"))
def sddmm_pallas(a, b, row_idx, col_idx, m_b: int = 256,
                 interpret: bool = False):
    """a: (Ra, N); b: (Rb, N); row/col_idx: (M,) int32 → (M,) a.dtype
    (fp32-accumulated — see module docstring). Rows are gathered one
    128-word feature tile at a time (the grid's second axis)."""
    m = row_idx.shape[0]
    m_pad = _round_up(max(m, 1), m_b)

    # +1 guard row each, gathered by the padding edges
    aw = to_words(jnp.pad(a, ((0, 1), (0, 0))), tiles_first=True)
    bw = to_words(jnp.pad(b, ((0, 1), (0, 0))), tiles_first=True)
    # row/col ids drive DMA addresses: SMEM scalar streams, one chunk each
    ridx = chunk_stream(row_idx.astype(jnp.int32), m_pad, m_b, a.shape[0])
    cidx = chunk_stream(col_idx.astype(jnp.int32), m_pad, m_b, b.shape[0])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(m_pad // m_b, aw.shape[0]),
        in_specs=[
            stream_spec(m_b, lambda i, j: i, smem=True),
            stream_spec(m_b, lambda i, j: i, smem=True),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=stream_spec(m_b, lambda i, j: i, smem=False),
        scratch_shapes=[pltpu.VMEM((m_b, LANES), aw.dtype),
                        pltpu.VMEM((m_b, LANES), bw.dtype),
                        pltpu.SemaphoreType.DMA],
    )
    out = pl.pallas_call(
        functools.partial(_body, io_dtype=a.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad // m_b, 1, m_b), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="sddmm",
    )(ridx, cidx, aw, bw)
    return out.reshape(m_pad)[:m].astype(a.dtype)
