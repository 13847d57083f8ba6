"""Jit'd public wrappers around the Pallas kernels.

On a TPU the kernels compile through Mosaic. On the CPU backend (the test
suite, ``JAX_PLATFORMS=cpu``) they run in the Pallas interpreter. Any other
backend is refused rather than interpreted in silence.

Every wrapper accepts the same ``(plan=, config=, tune=)`` trio with one
precedence (paper §III-C + the measured tier of :mod:`repro.core.autotune`;
documented once in ``docs/plans.md``):

    ``plan``  >  explicit ``config=``  >  measured PerfDB entry
    (``tune=True`` / ``REPRO_AUTOTUNE=1``)  >  generated decision-tree
    rules  >  hand-crafted

A plan's schedule metadata is authoritative: an explicit config may refine
non-tiling dimensions but must agree with the plan's tiling (conflicts
raise); ``tune`` is only consulted when neither a plan nor a config pins
the choice. Resolution happens *here*, outside the jitted pallas_call
wrappers, so a wall-clock tuning sweep never runs at trace time.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import threading
from typing import Optional

import jax

from repro.core.config_space import KernelConfig
from repro.kernels.gather_segment_reduce import gather_segment_reduce_pallas
from repro.kernels.segment_matmul import segment_matmul_pallas
from repro.kernels.segment_reduce import (flat_grid_steps,
                                          segment_reduce_pallas)


def _default_interpret() -> bool:
    """Compiled on a TPU, interpreted on the CPU; no other backend runs
    the kernels."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"the Pallas kernels run on a TPU (or, "
                           f"interpreted, on the CPU), not on {backend!r}")
    return backend == "cpu"


# ---------------------------------------------------------------------------
# fusion accounting — trace-time counters keyed "<kind>:<op>":
#   fused:    a fused Pallas kernel launch
#   unfused:  a jnp segment-op fallback replacing a fused aggregation
#   merge:    cross-shard halo algebra (e.g. the sharded softmax's (m, z)
#             statistics) — auxiliary segment ops that are part of the
#             collective merge, not a fallback of the aggregation itself
# Because the wrappers run at trace time, a jitted graph records each op
# site once; reset before tracing and read after to audit a path (e.g.
# assert the sharded message-passing path launches only fused kernels).
#
# Concurrency: the store is lock-guarded and scopes are contextvar-scoped
# per thread/context — a PrefetchPipeline producer thread tracing in the
# background can never leak its events into a consumer's fusion_scope()
# (each thread folds into its own innermost scope; threads without a
# scope fold into the process-global counter). Every event is also
# mirrored into the repro.obs metrics registry ("kernel.launches") so
# launch counts and fused-vs-unfused ratios land in the same telemetry
# dump as everything else.
# ---------------------------------------------------------------------------

_FUSION_LOCK = threading.Lock()
_FUSION_GLOBAL: collections.Counter = collections.Counter()
_FUSION_SCOPES: contextvars.ContextVar = contextvars.ContextVar(
    "repro_fusion_scopes", default=())


def _fusion_sink() -> collections.Counter:
    scopes = _FUSION_SCOPES.get()
    return scopes[-1] if scopes else _FUSION_GLOBAL


_LAUNCH_METRIC = None


def _launch_metric():
    global _LAUNCH_METRIC
    if _LAUNCH_METRIC is None:
        from repro.obs import get_registry
        _LAUNCH_METRIC = get_registry().counter(
            "kernel.launches", labels=("kind", "op"),
            help="trace-time kernel launch accounting "
                 "(fused/unfused/merge)")
    return _LAUNCH_METRIC


def account(kind: str, op: str, grid_steps: Optional[int] = None) -> None:
    """Record one ``kind`` ∈ {"fused", "unfused", "merge"} event on ``op``.
    ``grid_steps``: the (output block, chunk) steps a planned launch walks,
    recorded into the innermost :func:`launch_manifest` of the thread."""
    with _FUSION_LOCK:
        _fusion_sink()[f"{kind}:{op}"] += 1
    _launch_metric().inc(kind=kind, op=op)
    manifests = _MANIFESTS.get()
    if grid_steps is not None and manifests:
        entry = manifests[-1].setdefault(op, [0, 0])
        entry[0] += 1
        entry[1] += grid_steps


# ---------------------------------------------------------------------------
# launch manifests — what one traced program launches, for per-execution
# accounting: the trace records each planned launch once, and whoever runs
# the executable (the trainer) adds the manifest once per execution
# ---------------------------------------------------------------------------

_MANIFESTS: contextvars.ContextVar = contextvars.ContextVar(
    "repro_launch_manifests", default=())


@contextlib.contextmanager
def launch_manifest():
    """Collect the planned launches traced inside the block (this thread
    only): yields ``{op: [launches, grid steps walked]}``, the (output
    block, chunk) steps of the plan each launch was traced with, as
    :func:`_grid_steps` counts them. Feature tiles and the softmax's two
    passes repeat every step alike and are not counted."""
    manifest: dict = {}
    token = _MANIFESTS.set(_MANIFESTS.get() + (manifest,))
    try:
        yield manifest
    finally:
        _MANIFESTS.reset(token)


def _grid_steps(plan, num_segments: int, max_chunks: Optional[int],
                flat: bool = False) -> Optional[int]:
    """(output block, chunk) steps a launch with ``plan`` walks: the flat
    grid's T for the kernels that walk only owned steps (``flat``), else
    out_blocks × ``max_chunks``. None without a segment plan (its chunk
    counts are then made on the device and unknown here)."""
    if plan is None or not hasattr(plan, "chunk_count"):
        return None
    out_blocks = -(-int(num_segments) // plan.config.s_b)
    max_chunks = int(max_chunks or plan.max_chunks)
    if flat:
        return flat_grid_steps(plan.worst_case_chunks, out_blocks,
                               max_chunks)
    return out_blocks * max_chunks


def fusion_counts() -> dict:
    """Snapshot of the accounting counters (trace-time launch counts) —
    the innermost :func:`fusion_scope` of the calling thread, else the
    process-global store."""
    with _FUSION_LOCK:
        return dict(_fusion_sink())


def reset_fusion_counts() -> None:
    with _FUSION_LOCK:
        _fusion_sink().clear()


@contextlib.contextmanager
def fusion_scope():
    """Scoped fusion accounting: inside the block the counters start at
    zero and only record events of the block; on exit the scope's events
    are folded back into the enclosing counters, so global accounting
    still accumulates. Yields the scope's live Counter — read it at the
    end of the block (or via :func:`fusion_counts` inside it).

    This is what per-request accounting needs (e.g. the serving engine's
    per-request fusion audit): without a scope, every request's trace
    events pile onto one process-wide counter and no per-request
    attribution is possible. Scopes nest, and they are **contextvar-
    scoped**: a scope only captures events of its own thread/context, so
    concurrent producer threads (repro.data.pipeline) keep folding into
    the global store instead of interleaving into an unrelated scope."""
    inner = collections.Counter()
    outer_scopes = _FUSION_SCOPES.get()
    token = _FUSION_SCOPES.set(outer_scopes + (inner,))
    try:
        yield inner
    finally:
        _FUSION_SCOPES.reset(token)
        with _FUSION_LOCK:
            (outer_scopes[-1] if outer_scopes else _FUSION_GLOBAL
             ).update(inner)


def _resolve_config(config: Optional[KernelConfig], plan, idx_size: int,
                    num_segments: int, feat: int, op: str,
                    tune: Optional[bool] = None,
                    io_dtype=None) -> Optional[KernelConfig]:
    """Apply the selection precedence ahead of the jit boundary
    (plan > config > tune > heuristics).

    Returns None only when a plan carries the config (the kernel merges it
    with the plan's chunk metadata via ``_resolve_plan``). ``io_dtype``
    (a dtype or name) routes the measured tier to the right PerfDB
    precision shelf."""
    if config is not None or plan is not None:
        return config
    from repro.core.config_space import canonical_io_dtype
    from repro.core.heuristics import select_config
    return select_config(int(idx_size), int(num_segments), int(feat), op=op,
                         tune=tune,
                         io_dtype=canonical_io_dtype(io_dtype or "float32"))


def segment_reduce(x, idx, num_segments: int, reduce: str = "sum",
                   config: Optional[KernelConfig] = None,
                   max_chunks: Optional[int] = None,
                   interpret: Optional[bool] = None, plan=None,
                   tune: Optional[bool] = None):
    interpret = _default_interpret() if interpret is None else interpret
    config = _resolve_config(config, plan, x.shape[0], num_segments,
                             x.shape[-1], "segment_reduce", tune,
                             io_dtype=x.dtype)
    account("fused", f"segment_reduce_{reduce}",
            _grid_steps(plan, num_segments, max_chunks))
    if reduce == "mean":
        # the non-gather mean pairs a fused sum launch with a jnp count
        account("unfused", "segment_reduce_mean_count")
    return segment_reduce_pallas(x, idx, num_segments, reduce=reduce,
                                 config=config, max_chunks=max_chunks,
                                 interpret=interpret, plan=plan)


def gather_segment_reduce(h, gather_idx, seg_idx, num_segments: int,
                          weight=None, reduce: str = "sum",
                          config: Optional[KernelConfig] = None,
                          max_chunks: Optional[int] = None,
                          interpret: Optional[bool] = None, plan=None,
                          tune: Optional[bool] = None):
    """Fused gather + segment reduction, one launch per reduce ∈
    {sum, mean, max} (weighted or not) — the mean's count and the max's
    running maximum live inside the kernel, never as a second launch."""
    if reduce not in ("sum", "mean", "max"):
        raise ValueError(f"unknown reduce: {reduce!r} "
                         "(fused gather supports sum/mean/max)")
    interpret = _default_interpret() if interpret is None else interpret
    op = ("gather_segment_reduce" if reduce == "sum"
          else f"gather_segment_reduce_{reduce}")
    config = _resolve_config(config, plan, gather_idx.shape[0], num_segments,
                             h.shape[-1], op, tune, io_dtype=h.dtype)
    account("fused", op if weight is None else f"{op}_weighted",
            _grid_steps(plan, num_segments, max_chunks, flat=True))
    return gather_segment_reduce_pallas(h, gather_idx, seg_idx, num_segments,
                                        weight=weight, reduce=reduce,
                                        config=config, max_chunks=max_chunks,
                                        interpret=interpret, plan=plan)


def fused_transform_reduce(h, w, gather_idx, seg_idx, num_segments: int,
                           weight=None, reduce: str = "sum",
                           config: Optional[KernelConfig] = None,
                           max_chunks: Optional[int] = None,
                           interpret: Optional[bool] = None, plan=None,
                           tune: Optional[bool] = None):
    """One-launch SpMM+GEMM: Y[s] = (reduce_{seg[i]==s} wt[i]·H[gidx[i]]) @ W
    — the per-layer dense transform fused into the gather-reduce launch, so
    neither the (|E|, d) edge tensor nor the (S, d_in) aggregate is ever
    materialized. Linear reduces only (sum / mean)."""
    if reduce not in ("sum", "mean"):
        raise ValueError(f"unknown reduce: {reduce!r} "
                         "(fused transform-reduce supports sum/mean)")
    from repro.kernels.fused_transform_reduce import \
        fused_transform_reduce_pallas
    interpret = _default_interpret() if interpret is None else interpret
    config = _resolve_config(config, plan, gather_idx.shape[0], num_segments,
                             h.shape[-1], "fused_transform_reduce", tune,
                             io_dtype=h.dtype)
    account("fused", "fused_transform_reduce"
            if weight is None else "fused_transform_reduce_weighted",
            _grid_steps(plan, num_segments, max_chunks, flat=True))
    return fused_transform_reduce_pallas(h, w, gather_idx, seg_idx,
                                         num_segments, weight=weight,
                                         reduce=reduce, config=config,
                                         max_chunks=max_chunks,
                                         interpret=interpret, plan=plan)


def segment_matmul(x, group_sizes, w, config: Optional[KernelConfig] = None,
                   max_groups: Optional[int] = None,
                   interpret: Optional[bool] = None, plan=None,
                   tune: Optional[bool] = None):
    """Grouped GEMM over contiguous row groups — one launch for every
    relation/expert.

    ``plan=`` accepts a :class:`~repro.core.plan.RelationPlan`: its
    precomputed ``offsets`` / ``first_group`` / ``group_count`` leaves
    become the kernel's scalar-prefetch operands (no per-call
    searchsorted) and its tight ``max_groups`` bounds the grid's group
    dimension. A :class:`~repro.core.plan.SegmentPlan` is still accepted
    for backward compatibility (config only — its chunk metadata describes
    a segment index, not group offsets)."""
    interpret = _default_interpret() if interpret is None else interpret
    meta = {}
    if plan is not None and hasattr(plan, "first_group"):
        plan.validate(int(x.shape[0]), int(group_sizes.shape[0]))
        if config is None:
            config = plan.config
        elif (config.m_b, config.n_b) != (plan.config.m_b, plan.config.n_b):
            raise ValueError(
                f"explicit config (m_b={config.m_b}, n_b={config.n_b}) "
                f"conflicts with RelationPlan tiling "
                f"(m_b={plan.config.m_b}, n_b={plan.config.n_b})")
        if max_groups is None:
            max_groups = plan.max_groups
        meta = dict(offsets=plan.offsets, first_group=plan.first_group,
                    group_count=plan.group_count)
    elif config is None and plan is not None:
        config = plan.config
    if config is None:
        from repro.core.heuristics import select_config
        config = select_config(int(x.shape[0]), int(group_sizes.shape[0]),
                               int(w.shape[-1]), op="segment_matmul",
                               tune=tune)
    account("fused", "segment_matmul")
    return segment_matmul_pallas(x, group_sizes, w, m_b=config.m_b,
                                 n_b=config.n_b, max_groups=max_groups,
                                 interpret=interpret, **meta)


def sddmm(a, b, row_idx, col_idx, config: Optional[KernelConfig] = None,
          interpret: Optional[bool] = None, plan=None,
          tune: Optional[bool] = None):
    """Per-edge dot products. ``plan=`` is accepted for API symmetry with
    the reduction ops: only its selected config is consumed (SDDMM is a
    pure gather — a SegmentPlan's chunk metadata describes a sorted segment
    index, which SDDMM neither requires nor reads)."""
    from repro.kernels.sddmm import sddmm_pallas
    interpret = _default_interpret() if interpret is None else interpret
    if config is None and plan is not None:
        config = plan.config
    if config is None:
        from repro.core.heuristics import select_config
        config = select_config(int(row_idx.shape[0]), int(a.shape[0]),
                               int(a.shape[-1]), op="sddmm", tune=tune)
    return sddmm_pallas(a, b, row_idx, col_idx, m_b=config.m_b,
                        interpret=interpret)


def segment_softmax(x, idx, num_segments: int,
                    config: Optional[KernelConfig] = None,
                    max_chunks: Optional[int] = None,
                    interpret: Optional[bool] = None, plan=None,
                    tune: Optional[bool] = None):
    """Fused plan-aware softmax within sorted segments ((M,) or (M, H))."""
    from repro.kernels.segment_softmax import segment_softmax_pallas
    interpret = _default_interpret() if interpret is None else interpret
    feat = int(x.shape[-1]) if x.ndim > 1 else 1
    config = _resolve_config(config, plan, idx.shape[0], num_segments, feat,
                             "segment_softmax", tune, io_dtype=x.dtype)
    account("fused", "segment_softmax",
            _grid_steps(plan, num_segments, max_chunks))
    return segment_softmax_pallas(x, idx, num_segments, config=config,
                                  max_chunks=max_chunks, interpret=interpret,
                                  plan=plan)
