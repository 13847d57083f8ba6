"""repro.obs — the unified telemetry subsystem (``docs/observability.md``).

Three pillars, zero dependencies beyond the stdlib:

  * **metrics registry** (:mod:`repro.obs.registry`) — thread-safe
    Counter/Gauge/Histogram with labels; exact p50/p95/p99 over a bounded
    sample window; snapshot + delta; JSON-lines and Prometheus export
    (:mod:`repro.obs.export`).
  * **tracing spans** (:mod:`repro.obs.trace`) — ``span("serve.step")``
    context managers building per-request / per-step span trees across
    sample → pad → plan_cache → stamp → device_put → compile → execute,
    with a ring-buffer trace log and Chrome ``trace_event`` export; once
    jax is loaded each span is also a ``jax.profiler`` annotation, and
    jax's compile phases land under the span that paid them.
  * **attribution hooks** (:mod:`repro.obs.hooks`) — every jit trace,
    plan-cache miss, PerfDB tune, and bucket probe records a structured
    cause, so ``why_compiled()`` answers "why did step 37 compile?".

The pre-existing counter APIs (``fusion_counts``, ``CacheStats``,
``GNNServer.stats``, ``PrefetchPipeline.stats``, ``Trainer.traces``) are
views over this registry — their instruments are *vital* and keep
counting even when :func:`disable` switches the optional instrumentation
(spans, launch mirrors, attribution) off. Nothing here ever runs inside
a traced function: instrumentation is host-side only.

Environment:

  * ``REPRO_OBS=0``            — start disabled (overhead ≈ flag checks)
  * ``REPRO_METRICS_PATH``     — periodic + at-exit JSON-lines flush
  * ``REPRO_METRICS_EVERY_S``  — flush period (default 30)
  * ``REPRO_TRACE_PATH``       — Chrome trace JSON written at exit
"""
from __future__ import annotations

import atexit
import os

from repro.obs import export, hooks, registry, trace
from repro.obs.export import (start_flusher, stop_flusher, to_jsonl,
                              to_prometheus, write_jsonl, write_prometheus)
from repro.obs.hooks import (attributions, record_cache_event,
                             record_compile, record_probe, record_tune,
                             reset_events, why_compiled)
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                get_registry, next_id)
from repro.obs.trace import (Span, chrome_trace, current_span, reset_spans,
                             span, spans, write_chrome_trace)

__all__ = [
    "registry", "trace", "hooks", "export",
    # registry
    "get_registry", "next_id", "Counter", "Gauge", "Histogram",
    "MetricsRegistry",
    # spans
    "span", "spans", "current_span", "reset_spans", "Span",
    "chrome_trace", "write_chrome_trace",
    # attribution
    "record_compile", "record_cache_event", "record_tune", "record_probe",
    "attributions", "why_compiled", "reset_events",
    # export
    "to_jsonl", "write_jsonl", "to_prometheus", "write_prometheus",
    "start_flusher", "stop_flusher",
    # switch + summaries
    "enable", "disable", "enabled", "report", "reset", "OBS_SCHEMA",
]


# ---------------------------------------------------------------------------
# the documented metric schema — renames break this table first
# (tests/test_obs.py pins it; dashboards and check_metrics.py read it)
# ---------------------------------------------------------------------------

OBS_SCHEMA = {
    # kernel launch accounting (trace-time, mirrors fusion_counts)
    "kernel.launches":            ("kind", "op"),
    # (output block, chunk) grid steps per executed planned launch:
    # kind = walked (out_blocks × max_chunks) | owned (Σ chunk_count)
    "kernel.grid_steps":          ("op", "kind"),
    # serving engine (one label value per GNNServer instance)
    "serve.requests":             ("engine",),
    "serve.batches":              ("engine",),
    "serve.serve_s":              ("engine",),
    "serve.compiles":             ("engine",),
    "serve.request_latency_s":    ("engine",),
    "serve.queue_s":              ("engine",),
    "serve.pad_node_frac":        ("engine",),
    "serve.pad_edge_frac":        ("engine",),
    # batcher admission
    "serve.submitted":            ("batcher",),
    "serve.queue_depth":          ("batcher",),
    # plan/executable cache (one label value per PlanCache instance)
    "serve.plan_cache.hits":         ("cache",),
    "serve.plan_cache.misses":       ("cache",),
    "serve.plan_cache.evictions":    ("cache",),
    "serve.plan_cache.prefills":     ("cache",),
    "serve.plan_cache.plan_builds":  ("cache",),
    "serve.plan_cache.compiles":     ("cache",),
    "serve.plan_cache.plan_build_s": ("cache",),
    "serve.plan_cache.compile_s":    ("cache",),
    # out-of-core pipeline (one label value per PrefetchPipeline)
    "pipeline.batches":           ("pipeline",),
    "pipeline.sync_falls":        ("pipeline",),
    "pipeline.wait_s":            ("pipeline",),
    "pipeline.produce_s":         ("pipeline",),
    # trainer (one label value per Trainer instance)
    "train.steps":                ("trainer",),
    "train.traces":               ("trainer",),
    # attribution counters
    "compile.events":             ("site", "cause"),
    # jax's compile phases (trace / lower / compile) and the persistent
    # compilation cache's outcome (hit / miss), from jax.monitoring
    "compile.phase_s":            ("phase",),
    "compile.cache":              ("outcome",),
    "autotune.tunes":             ("op", "outcome"),
}


# ---------------------------------------------------------------------------
# switch
# ---------------------------------------------------------------------------

def enable() -> None:
    """Switch the optional instrumentation (spans, launch mirrors,
    attribution events) on. Vital counters always count."""
    registry._set_enabled(True)


def disable() -> None:
    """Switch the optional instrumentation off; per-call cost drops to a
    flag check. The public counter APIs keep working (vital)."""
    registry._set_enabled(False)


def enabled() -> bool:
    return registry._is_enabled()


def reset() -> None:
    """Zero metrics, drop spans and attribution events. Registered
    instruments keep their handles (safe for live engines)."""
    get_registry().reset()
    reset_spans()
    reset_events()


# ---------------------------------------------------------------------------
# human summary
# ---------------------------------------------------------------------------

def report() -> str:
    """A human-readable telemetry summary: counters grouped by prefix,
    histogram quantiles, and the most recent compile attributions."""
    reg = get_registry()
    lines = ["== repro.obs report =="]
    snap = reg.snapshot()
    by_prefix: dict = {}
    for row in snap:
        by_prefix.setdefault(row["name"].split(".")[0], []).append(row)
    for prefix in sorted(by_prefix):
        lines.append(f"[{prefix}]")
        for row in by_prefix[prefix]:
            lab = ",".join(f"{k}={v}" for k, v in row["labels"].items())
            lab = f"{{{lab}}}" if lab else ""
            if row["type"] == "histogram":
                lines.append(
                    f"  {row['name']}{lab}  n={row['count']} "
                    f"mean={row['mean']:.6f} p50={row['p50']:.6f} "
                    f"p95={row['p95']:.6f} p99={row['p99']:.6f}")
            else:
                v = row["value"]
                v = int(v) if float(v).is_integer() else v
                lines.append(f"  {row['name']}{lab} = {v}")
    compiles = why_compiled()
    if compiles:
        lines.append(f"[attribution] {len(compiles)} compiles recorded; "
                     "most recent:")
        for e in compiles[-8:]:
            detail = {k: v for k, v in e.items()
                      if k not in ("kind", "site", "cause", "t_s")}
            lines.append(f"  {e['site']} <- {e['cause']} {detail}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# environment wiring
# ---------------------------------------------------------------------------

if os.environ.get("REPRO_OBS", "1") in ("0", "false", "False"):
    disable()

_METRICS_PATH = os.environ.get("REPRO_METRICS_PATH")
if _METRICS_PATH:
    start_flusher(_METRICS_PATH,
                  float(os.environ.get("REPRO_METRICS_EVERY_S", "30")))
    atexit.register(stop_flusher)

_TRACE_PATH = os.environ.get("REPRO_TRACE_PATH")
if _TRACE_PATH:
    atexit.register(lambda: write_chrome_trace(_TRACE_PATH))
