"""Mosaic-legal operand layouts shared by the chunked Pallas kernels.

Three rules of the TPU compiler shape every kernel in this package:

* **(8, 128) blocks.** The last two dims of a block must be multiples of
  (8, 128) or equal the array's. A (1, M_b) block of an (chunks, M_b)
  index array is refused, so each per-edge stream is laid out
  (chunks, 1, M_b) and read one chunk at a time through a squeezed
  (None, 1, M_b) block (:func:`chunk_stream`, :func:`stream_spec`).
* **Scalars from SMEM.** One element of a VMEM vector cannot be read at a
  dynamic lane. Indices that drive DMA addresses or a sequential walk are
  SMEM streams; vectors that build a one-hot stay in VMEM.
* **Single-row DMA of 32-bit, 128-lane rows only.** A one-row slice is
  accepted when the row is one contiguous (8, 128) tile row of 32-bit
  words: a wider row spans several tiles, and a bf16 row shares its
  32-bit words with the next row. Gathered or dynamically indexed rows
  therefore travel as *words*: ``(…, 128)`` tiles of 32-bit words, one
  fp32 value per word, or two bf16 values per word (:func:`to_words`).
  Kernels unpack a tile of words into ``128·k`` fp32 columns in their
  original order (:func:`unpack_words`, k = 1 for fp32 and 2 for bf16), so
  bf16 io keeps its half-width HBM reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config_space import LANES, VMEM_BYTES, _round_up

# ---------------------------------------------------------------------------
# per-chunk index / weight streams
# ---------------------------------------------------------------------------

def chunk_stream(a, m_pad: int, m_b: int, fill):
    """(M,) → (m_pad // m_b, 1, m_b): padded with ``fill``, one chunk per
    leading index."""
    a = jnp.pad(a, (0, m_pad - a.shape[0]), constant_values=fill)
    return a.reshape(m_pad // m_b, 1, m_b)


def stream_spec(m_b: int, chunk_of, *, smem: bool) -> pl.BlockSpec:
    """Block over a :func:`chunk_stream`; ``chunk_of(*grid_idx, *prefetch)``
    names the chunk. The kernel sees a (1, m_b) ref."""
    return pl.BlockSpec((None, 1, m_b), lambda *g: (chunk_of(*g), 0, 0),
                        memory_space=pltpu.SMEM if smem else None)


def compiler_params() -> pltpu.CompilerParams:
    """Pin the scoped VMEM limit the config space is sized against."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES)


# ---------------------------------------------------------------------------
# feature rows as 32-bit words
# ---------------------------------------------------------------------------

def word_ratio(dtype) -> int:
    """Values per 32-bit word: 1 for fp32, 2 for bf16."""
    k = 4 // jnp.dtype(dtype).itemsize
    if k not in (1, 2):
        raise ValueError(f"unsupported io dtype {jnp.dtype(dtype).name}")
    return k


def word_cols(n: int, dtype) -> int:
    """Feature width padded to whole 128-word tiles, in values."""
    return _round_up(max(n, 1), LANES * word_ratio(dtype))


def to_words(x, *, tiles_first: bool):
    """(R, n) io dtype → 32-bit words, zero-padded to whole 128-word tiles.

    A bf16 word holds column c of a 256-column group in its low half and
    column c + 128 in its high half, so unpacking a tile yields the
    group's columns in order. ``tiles_first=False`` gives (R, W) for
    BlockSpec-tiled operands; ``tiles_first=True`` gives (W // 128, R, 128)
    for row-gathered HBM operands, so that one tile of one row is a
    contiguous 512-byte DMA."""
    r, n = x.shape
    x = jnp.pad(x, ((0, 0), (0, word_cols(n, x.dtype) - n)))
    if word_ratio(x.dtype) == 2:
        u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        u = u.reshape(r, -1, 2, LANES)
        x = jax.lax.bitcast_convert_type(u[:, :, 0] | (u[:, :, 1] << 16),
                                         jnp.int32)
    x = x.reshape(r, -1, LANES)
    return x.transpose(1, 0, 2) if tiles_first else x.reshape(r, -1)


def unpack_words(w, dtype):
    """In-kernel: (rows, 128·t) words → (rows, 128·t·k) fp32 columns in
    their original order (k = 2 for bf16; fp32 words are the values)."""
    if word_ratio(dtype) == 1:
        return w
    f32 = lambda u: jax.lax.bitcast_convert_type(u, jnp.float32)  # noqa: E731
    pieces = []
    for t in range(w.shape[-1] // LANES):
        u = w[:, t * LANES:(t + 1) * LANES]
        pieces += [f32(jax.lax.shift_left(u, 16)), f32(u & jnp.int32(-65536))]
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)


# ---------------------------------------------------------------------------
# in-kernel one-hot and matmul
# ---------------------------------------------------------------------------

def onehot_t(idx_ref, b, s_b: int):
    """(S_b, M_b) transposed one-hot of a (1, M_b) VMEM segment-id block:
    ``hit[r, i] = seg[i] == b·S_b + r``. Built with the ids along lanes, so
    the chunk reduces as one plain ``hit @ X`` matmul and no id vector is
    ever relaid out across sublanes; ids outside block b's window (foreign
    or padding rows) give all-false columns."""
    rel = idx_ref[...] - b * s_b
    rows = jax.lax.broadcasted_iota(jnp.int32, (s_b, rel.shape[1]), 0)
    return rows == rel


def weighted_onehot(hit, w_ref, dtype):
    """The one-hot as an MXU operand of ``dtype``, each column scaled by
    its edge weight (a (1, M_b) VMEM block) when ``w_ref`` is given."""
    w = 1.0 if w_ref is None else w_ref[...].astype(jnp.float32)
    return jnp.where(hit, w, 0.0).astype(dtype)


def mxu_dot(a, b):
    """``a @ b`` with fp32 accumulation; fp32 operands run at full
    precision (the MXU's default truncates them to bf16)."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=prec,
                               preferred_element_type=jnp.float32)
