"""LRU cache of per-bucket plan templates and jit executables.

The hot-path problem this solves: a :class:`~repro.core.plan.SegmentPlan`
is a pytree whose *static aux* (kernel config, tight ``max_chunks``,
degree stats) differs per graph — so even two graphs padded to the same
(V, E) bucket would retrace a jitted forward if each brought its own
plan. A :class:`BucketEntry` therefore canonicalizes everything static
**per bucket**:

  * one :class:`~repro.core.config_space.KernelConfig`, resolved once per
    bucket — a measured PerfDB winner when one exists for the bucket's
    shape class (:func:`measured_config`; a pure lookup, never an inline
    sweep), else the generated decision-tree rules;
  * ``max_chunks`` pinned to a bucket-static bound (see ``chunk_policy``
    on the engine) instead of the per-graph tight value;
  * canonical per-bucket :class:`~repro.core.plan.SegmentStats` (skew 1),
    so cost-model decisions (transform/aggregate order) are a function of
    the bucket, not the request.

Per request, only the plan's *leaves* change: :meth:`BucketEntry.stamp`
recomputes the chunk metadata (one ``searchsorted`` over the padded
destinations) and grafts it onto the template — zero ``make_plan`` /
config-selection / compile work on a cache hit, which the counters (and
the tests) verify.

The cache is capacity-bounded LRU: evicting an entry drops its executable
(recompiled on next touch, counted as a fresh miss). ``warm`` prefills
entries ahead of traffic without polluting the hit/miss accounting.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Dict, Hashable, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.config_space import KernelConfig
from repro.core.plan import SegmentPlan, SegmentStats
from repro.serve.buckets import ShapeBucket

__all__ = ["CacheStats", "BucketEntry", "PlanCache", "measured_config",
           "bucket_max_chunks"]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def measured_config(bucket: ShapeBucket, feat: int,
                    op: str = "segment_reduce",
                    db=None) -> Optional[KernelConfig]:
    """The PerfDB's measured winner for the bucket's shape class, or None.

    This is the serving tier of the selection precedence: a *lookup only*
    — serving must never pay a wall-clock sweep inline. Populate the DB
    offline (``tune=True`` plan builds, the ablation benchmark, or
    :meth:`GNNServer.warmup` with ``tune=True``)."""
    import jax

    from repro.core import autotune
    from repro.core.features import InputFeatures

    if db is None:
        db = autotune.PerfDB()
    backend = jax.default_backend()
    feats = InputFeatures(int(bucket.num_edges), int(bucket.num_nodes),
                          int(feat))
    entry = db.get(autotune.perf_key(backend, op, feats))
    if entry is None:
        return None
    return KernelConfig(*entry["best"])


def bucket_max_chunks(bucket: ShapeBucket, config: KernelConfig,
                      policy: str = "worst") -> int:
    """Bucket-static chunk-grid bound.

    ``"worst"`` — every row block (``ceil(E_bucket / m_b)``): one compile
    per bucket, guaranteed to cover any graph in it (a block's chunk range
    is a subrange of all chunks). The tight per-graph grid is traded for
    executable reuse — the serving latency/predictability tradeoff
    (``docs/serving.md``). No other policy is bucket-static; growth
    policies live in the engine."""
    if policy != "worst":
        raise ValueError(f"unknown bucket-static chunk policy {policy!r}")
    m_pad = _round_up(max(bucket.num_edges, 1), config.m_b)
    return max(m_pad // config.m_b, 1)


def _canonical_stats(bucket: ShapeBucket) -> SegmentStats:
    """Deterministic per-bucket stats (skew 1): cost-model decisions made
    from a template must match for every graph in the bucket, or the
    traced program (transform/aggregate order) would differ per request."""
    e, v = bucket.num_edges, bucket.num_nodes
    live = max(min(e, v), 1)
    avg = e / live
    return SegmentStats(num_rows=e, num_segments=v, live_segments=live,
                        max_degree=max(int(np.ceil(avg)), 1),
                        avg_degree=avg, std_degree=0.0)


class BucketEntry:
    """One cache line: the bucket's canonical plan template + (set by the
    engine) the jit executable compiled against its static aux."""

    def __init__(self, bucket: ShapeBucket, feat: int, config: KernelConfig,
                 max_chunks: Optional[int] = None):
        self.bucket = bucket
        self.feat = int(feat)
        self.config = config
        self.max_chunks = (bucket_max_chunks(bucket, config)
                           if max_chunks is None else int(max_chunks))
        self.m_pad = _round_up(max(bucket.num_edges, 1), config.m_b)
        # all-pad index: the template's leaves describe "no real edges";
        # stamp() replaces them with a request's actual chunk metadata
        self.template = self._stamp_plan(
            np.full(0, bucket.num_nodes, np.int32), template=None)
        self.executable = None        # attached by the engine
        self.compiled = False
        self.compile_s = 0.0

    # -- per-request leaves -------------------------------------------------
    def _stamp_plan(self, dst: np.ndarray, template) -> SegmentPlan:
        from repro.kernels.segment_reduce import chunk_metadata
        v, cfg = self.bucket.num_nodes, self.config
        idxp = np.full(self.m_pad, v, np.int32)
        idxp[:dst.size] = dst
        cf, cc = chunk_metadata(idxp, v, cfg.s_b, cfg.m_b, self.m_pad)
        if template is not None:
            return dataclasses.replace(template, chunk_first=jnp.asarray(cf),
                                       chunk_count=jnp.asarray(cc))
        return SegmentPlan(chunk_first=jnp.asarray(cf),
                           chunk_count=jnp.asarray(cc),
                           num_rows=self.bucket.num_edges,
                           num_segments=v,
                           max_chunks=self.max_chunks,
                           config=cfg,
                           stats=_canonical_stats(self.bucket))

    def stamp(self, dst) -> SegmentPlan:
        """A servable plan for one padded graph: the request's chunk
        metadata (leaves) under the bucket's static aux — same pytree
        treedef as the template, so the executable never retraces."""
        dst = np.asarray(dst, np.int32)
        if dst.size != self.bucket.num_edges:
            raise ValueError(
                f"stamp expects {self.bucket.num_edges} padded edges "
                f"(bucket {self.bucket}), got {dst.size}")
        return self._stamp_plan(dst, self.template)


class CacheStats:
    """Hit/miss/eviction + build/compile-time accounting — a view over
    labeled instruments in the :mod:`repro.obs` metrics registry. Each
    stats object carries a process-unique ``cache`` label, so every
    PlanCache's counters export side by side in one telemetry dump;
    the instruments are *vital* (they count even when observability is
    disabled — the serving contract's tests rely on them). Attribute
    reads/writes (``stats.hits += 1``) go straight through to the
    registry series."""

    _INT_FIELDS = ("hits", "misses", "evictions", "prefills",
                   "plan_builds", "compiles")
    _FLOAT_FIELDS = ("plan_build_s", "compile_s")

    def __init__(self, cache_id: Optional[str] = None):
        from repro import obs
        reg = obs.get_registry()
        self.cache_id = cache_id or obs.next_id("cache")
        self._labels = {"cache": self.cache_id}
        self._metrics = {
            f: reg.counter(f"serve.plan_cache.{f}", labels=("cache",),
                           vital=True)
            for f in self._INT_FIELDS + self._FLOAT_FIELDS}
        for m in self._metrics.values():
            m.touch(**self._labels)

    @property
    def lookups(self) -> int:
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def as_dict(self) -> Dict:
        d = {f: getattr(self, f)
             for f in self._INT_FIELDS + self._FLOAT_FIELDS}
        d["hit_rate"] = round(self.hit_rate, 4)
        return d


def _stats_field(field: str, as_int: bool):
    def fget(self):
        v = self._metrics[field].value(**self._labels)
        return int(v) if as_int else v

    def fset(self, v):
        self._metrics[field].set(float(v), **self._labels)

    return property(fget, fset)


for _f in CacheStats._INT_FIELDS:
    setattr(CacheStats, _f, _stats_field(_f, as_int=True))
for _f in CacheStats._FLOAT_FIELDS:
    setattr(CacheStats, _f, _stats_field(_f, as_int=False))
del _f


class PlanCache:
    """Capacity-bounded LRU over :class:`BucketEntry` cache lines.

    Keys are whatever tuple the caller serves under — the engine uses
    ``(bucket, feat, model, impl, shards)`` so one cache can back several
    engines. ``weight=`` on the counting methods attributes a lookup to
    the number of *requests* it served (a batch of k graphs sharing one
    bucket counts k hits), which is the hit-rate a serving SLO cares
    about.

    Thread-safe: the prefetch pipeline's producer threads
    (:mod:`repro.data.pipeline`) hit the same cache concurrently with the
    consumer, so every read-modify-write — LRU reorder, eviction, stats
    bump, and the build inside :meth:`get_or_build` — happens under one
    re-entrant lock. Holding the lock across the builder intentionally
    serializes misses on the same key: N racing threads produce exactly
    one ``BucketEntry`` (``plan_builds`` counts distinct keys, not
    threads), which is the invariant the zero-retrace accounting needs.
    """

    def __init__(self, capacity: int = 32):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self.stats = CacheStats()
        self._lock = threading.RLock()
        self._entries: "collections.OrderedDict[Hashable, BucketEntry]" = \
            collections.OrderedDict()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def keys(self):
        with self._lock:
            return list(self._entries)

    # -- core --------------------------------------------------------------
    def lookup(self, key: Hashable, weight: int = 1) -> Optional[BucketEntry]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.stats.misses += weight
                from repro import obs
                obs.record_cache_event(self.stats.cache_id, "miss",
                                       key=str(key), weight=weight)
                return None
            self._entries.move_to_end(key)
            self.stats.hits += weight
            return entry

    def insert(self, key: Hashable, entry: BucketEntry) -> None:
        with self._lock:
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                old_key, _ = self._entries.popitem(last=False)
                self.stats.evictions += 1
                from repro import obs
                obs.record_cache_event(self.stats.cache_id, "eviction",
                                       key=str(old_key),
                                       capacity=self.capacity)

    def get_or_build(self, key: Hashable,
                     builder: Callable[[], BucketEntry],
                     weight: int = 1) -> BucketEntry:
        """One serving lookup: LRU hit, or build + insert on miss (the
        build time lands in ``plan_build_s``; the *compile* happens on the
        entry's first execution and is accounted by the engine). The lock
        is held across the builder — concurrent misses on one key build
        once (the RLock makes a builder that re-enters the cache safe)."""
        with self._lock:
            entry = self.lookup(key, weight=weight)
            if entry is None:
                t0 = time.perf_counter()
                entry = builder()
                self.stats.plan_builds += 1
                self.stats.plan_build_s += time.perf_counter() - t0
                self.insert(key, entry)
            return entry

    def warm(self, key: Hashable,
             builder: Callable[[], BucketEntry]) -> BucketEntry:
        """Prefill ahead of traffic: like :meth:`get_or_build` but counted
        as a prefill, not a miss — warmup must not dilute the serving
        hit-rate it exists to protect."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                return entry
            t0 = time.perf_counter()
            entry = builder()
            self.stats.prefills += 1
            self.stats.plan_builds += 1
            self.stats.plan_build_s += time.perf_counter() - t0
            self.insert(key, entry)
            return entry
