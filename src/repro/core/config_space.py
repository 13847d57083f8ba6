"""Tunable hierarchical tiling space (paper §III-A/B, Table I), re-based on
TPU geometry.

Parameter mapping (GPU → TPU, see DESIGN.md §2):

    T_M, T_N  (thread groups / block)   →  S_b, N_b  (out-rows / cols per VMEM block)
    M_t, N_t  (data / thread group)     →  M_b       (input rows per chunk)
    G_t       (synced threads, PR only) →  K_c       (rows per MXU sub-matmul)
    schedule  (SR / PR)                 →  schedule  (VPU row-scan / MXU one-hot)

Like the paper (§III-C) we prune the space to a constant-size candidate set
grounded in hardware constraints: N_b multiples of the 128-lane register
width, M_b multiples of the 8-sublane height, and the VMEM budget
(double-buffered in + out + one-hot tiles ≤ VMEM_BYTES).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator, List

# the scoped VMEM limit every kernel is compiled with
# (kernels.layout.compiler_params); the largest config of the space
# compiles under it for a v5e (tests/test_mosaic_compile.py)
VMEM_BYTES = 16 * 1024 * 1024
LANES = 128                            # vector register lanes
SUBLANES = 8                           # vector register sublanes (fp32)

# --- io dtype axis -----------------------------------------------------------
# The kernels carry an *io dtype* (the dtype of x / weights / outputs in HBM)
# orthogonal to the accumulator dtype, which is always fp32. Lowering the io
# dtype halves the bytes per row-DMA on the bandwidth-bound gather/scatter
# stages — the paper's segment reduces are bandwidth-bound (§IV), so io dtype
# is a first-class tuning axis next to the tile sizes.
IO_DTYPES = ("float32", "bfloat16")

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def _io_dtype_name(dtype) -> str:
    name = getattr(dtype, "name", None)
    if isinstance(name, str):
        return name
    import numpy as np
    try:
        return np.dtype(dtype).name     # handles type classes (jnp.float32)
    except TypeError:
        return str(dtype)


def io_dtype_bytes(dtype) -> int:
    """Bytes per element of an io dtype (name, np.dtype, jax dtype, or
    scalar type class)."""
    name = _io_dtype_name(dtype)
    try:
        return _DTYPE_BYTES[name]
    except KeyError:
        import numpy as np
        return int(np.dtype(name).itemsize)


def canonical_io_dtype(dtype) -> str:
    """Canonical string name for the io dtype axis ('float32', 'bfloat16')."""
    return _io_dtype_name(dtype)


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """A point in the tunable space ⟨schedule, S_b, N_b, M_b, K_c⟩."""
    schedule: str = "SR"    # "SR" (VPU sequential) | "PR" (MXU one-hot)
    s_b: int = 128          # output rows per block (PR out-tile height)
    n_b: int = 128          # feature columns per block
    m_b: int = 256          # input rows per chunk
    k_c: int = 8            # MXU contraction sub-chunk (PR only; SR ⇒ 1)

    def __post_init__(self):
        if self.schedule == "SR":
            object.__setattr__(self, "k_c", 1)

    def astuple(self):
        return (self.schedule, self.s_b, self.n_b, self.m_b, self.k_c)

    def vmem_bytes(self, dtype_bytes: int = 4) -> int:
        """VMEM working set: X chunk + out block + one-hot (PR), x2 buffered."""
        x_tile = self.m_b * self.n_b * dtype_bytes
        out_tile = self.s_b * self.n_b * dtype_bytes
        onehot = self.m_b * self.s_b * dtype_bytes if self.schedule == "PR" else 0
        idx_tile = self.m_b * 4
        return 2 * (x_tile + idx_tile) + out_tile + onehot


# Tunable op keys: every kernel the selection tiers (PerfDB / generated
# rules / hand-crafted) may be asked about. The gather variants are distinct
# keys because their measured profiles differ (mean carries an in-kernel
# count, max forces the SR walk); segment_softmax consumes only (S_b, M_b).
OP_KEYS = (
    "segment_reduce",
    "gather_segment_reduce",
    "gather_segment_reduce_mean",
    "gather_segment_reduce_max",
    "segment_softmax",
    "segment_matmul",
    "grouped_segment_matmul",
    "sddmm",
    "fused_transform_reduce",
)

# Pruned candidate ranges (paper §III-C prunes to constant space; ours are
# anchored to (8,128) tiling and MXU dims instead of warp sizes).
SCHEDULES = ("SR", "PR")
S_B_CANDIDATES = (64, 128, 256)
N_B_CANDIDATES = (128, 256, 512)
M_B_CANDIDATES = (128, 256, 512, 1024)
K_C_CANDIDATES = (8, 16, 32)


def enumerate_configs(feat_dim: int | None = None,
                      dtype_bytes: int = 4) -> Iterator[KernelConfig]:
    """All valid configs (VMEM-feasible; N_b ≤ padded feature dim)."""
    for sched in SCHEDULES:
        kcs = (1,) if sched == "SR" else K_C_CANDIDATES
        for s_b, n_b, m_b, k_c in itertools.product(
                S_B_CANDIDATES, N_B_CANDIDATES, M_B_CANDIDATES, kcs):
            cfg = KernelConfig(sched, s_b, n_b, m_b, k_c)
            if cfg.vmem_bytes(dtype_bytes) > VMEM_BYTES:
                continue
            if k_c > m_b:
                continue
            if feat_dim is not None and n_b > max(LANES, _round_up(feat_dim, LANES)):
                continue
            yield cfg


def all_configs(feat_dim: int | None = None) -> List[KernelConfig]:
    return list(enumerate_configs(feat_dim))


def default_config(feat_dim: int = 128) -> KernelConfig:
    """Static fallback (the 'hand-crafted rule' baseline of Fig. 8):
    SR for F > 4 else PR, mirroring the paper's empirical rule."""
    if feat_dim > 4:
        return KernelConfig("SR", 128, min(512, _round_up(feat_dim, LANES)), 512, 1)
    return KernelConfig("PR", 128, 128, 256, 16)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
