"""Attribution hooks: every expensive or surprising event — a jit
trace, a plan-cache miss/eviction, an autotuner sweep, a bucket probe —
records a structured *cause*, so "why did step 37 compile?" is
answerable from the telemetry dump alone.

Events are plain dicts in a bounded ring (``attributions()``), each with
``kind`` / ``site`` / ``cause`` plus whatever structured detail the call
site attaches (op key, bucket, io_dtype, treedef hash, step). A counter
per (site, cause) lands in the metrics registry so dashboards can alert
on compile storms without parsing the ring.

Compile phases (:func:`watch_compiles`): jax reports each trace,
lowering and backend compile (or load from the persistent compilation
cache) through :mod:`jax.monitoring`. Each phase feeds the histogram
``compile.phase_s{phase}``, each cache hit or miss the counter
``compile.cache{outcome}``, and while a span is open in the compiling
thread the phase also lands under it as a completed child span
``jax.trace`` / ``jax.lower`` / ``jax.compile`` (the last with
``cache=hit|miss``). The listeners run on the host while jax compiles
and add nothing to the traced function.

Recording respects the observability switch (``repro.obs.disable()``
makes every hook a no-op); the public counter APIs these events annotate
(``CacheStats`` etc.) are vital and keep counting regardless.
"""
from __future__ import annotations

import collections
import os
import threading
import time
from typing import List, Optional

from repro.obs import registry as _registry
from repro.obs import trace as _trace

__all__ = ["record_compile", "record_cache_event", "record_tune",
           "record_probe", "attributions", "why_compiled", "reset_events",
           "watch_compiles"]

_RING_CAP = int(os.environ.get("REPRO_OBS_EVENTS", "1024"))
_EVENTS: collections.deque = collections.deque(maxlen=_RING_CAP)
_LOCK = threading.Lock()


def _counter(name, labels):
    return _registry.get_registry().counter(name, labels=labels)


def _record(kind: str, site: str, cause: str, detail: dict) -> None:
    if not _registry._is_enabled():
        return
    event = {"kind": kind, "site": site, "cause": cause,
             "t_s": time.time(), **detail}
    with _LOCK:
        _EVENTS.append(event)


def record_compile(site: str, cause: str, **detail) -> None:
    """One jit trace fired at ``site`` (serve.forward, train.step, ...)
    because of ``cause`` (warmup, bucket_miss, new_bucket, retrace,
    sampled_ingest, ...). Attach the bucket, op key, io_dtype, treedef
    hash — whatever identifies the traced program."""
    _counter("compile.events", ("site", "cause")).inc(
        site=site, cause=cause)
    _record("compile", site, cause, detail)


def record_cache_event(cache: str, cause: str, **detail) -> None:
    """A plan-cache miss or eviction on ``cache`` (the instance label the
    cache's counters carry). Hits are not recorded here — they are the
    steady state the counters already measure."""
    _record("cache", f"plan_cache:{cache}", cause, detail)


def record_tune(op: str, *, cache_hit: bool, timings: int = 0,
                **detail) -> None:
    """One autotuner consult: a warm PerfDB hit or a paid wall-clock
    sweep (``timings`` kernels executed)."""
    outcome = "hit" if cache_hit else "sweep"
    _counter("autotune.tunes", ("op", "outcome")).inc(op=op,
                                                      outcome=outcome)
    _record("tune", f"autotune:{op}", outcome,
            dict(detail, timings=timings))


def record_probe(site: str, bucket, **detail) -> None:
    """A bucket probe (e.g. warmup schedule discovery): which bucket a
    probed batch landed in, before any traffic pays for it."""
    _record("probe", site, "bucket_probe", dict(detail, bucket=str(bucket)))


def attributions(kind: Optional[str] = None) -> List[dict]:
    """The event ring, oldest first; ``kind`` filters (compile / cache /
    tune / probe)."""
    with _LOCK:
        events = list(_EVENTS)
    if kind is not None:
        events = [e for e in events if e["kind"] == kind]
    return events


def why_compiled() -> List[dict]:
    """Every recorded jit trace with its cause — the compile audit."""
    return attributions("compile")


def reset_events() -> None:
    with _LOCK:
        _EVENTS.clear()


# ---------------------------------------------------------------------------
# compile phases, from jax.monitoring
# ---------------------------------------------------------------------------

COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # also covers a load from the persistent compilation cache
    "/jax/core/compile/backend_compile_duration": "compile",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

_WATCHING = False
_WATCH_LOCK = threading.Lock()
_CACHE_HIT = threading.local()      # a hit seen in this thread's compile


def _on_phase(event: str, start_time: float, end_time: float,
              **kwargs) -> None:
    phase = COMPILE_PHASES.get(event)
    if phase is None or not _registry._is_enabled():
        return
    dur = end_time - start_time
    _registry.get_registry().histogram("compile.phase_s", ("phase",)).observe(
        dur, phase=phase)
    attrs = {"fun_name": kwargs.get("fun_name", "")}
    if phase == "compile":
        attrs["cache"] = "hit" if getattr(_CACHE_HIT, "hit", False) \
            else "miss"
        _CACHE_HIT.hit = False
    _trace.add_completed(f"jax.{phase}", _trace.span_clock(start_time), dur,
                         **attrs)


def _on_event(event: str, **kwargs) -> None:
    outcome = CACHE_EVENTS.get(event)
    if outcome is None or not _registry._is_enabled():
        return
    _counter("compile.cache", ("outcome",)).inc(outcome=outcome)
    if outcome == "hit":
        _CACHE_HIT.hit = True


def watch_compiles() -> None:
    """Register the compile-phase listeners with :mod:`jax.monitoring`,
    once per process (later calls do nothing). Imports jax: call it only
    where jax is already loaded — :func:`repro.obs.span` does, the first
    time it finds jax in ``sys.modules``."""
    global _WATCHING
    with _WATCH_LOCK:
        if _WATCHING:
            return
        import jax.monitoring
        reg = _registry.get_registry()
        reg.histogram("compile.phase_s", ("phase",),
                      help="jax trace / lower / backend compile seconds")
        reg.counter("compile.cache", ("outcome",),
                    help="persistent compilation cache hits and misses")
        jax.monitoring.register_event_time_span_listener(_on_phase)
        jax.monitoring.register_event_listener(_on_event)
        _WATCHING = True
