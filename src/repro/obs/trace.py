"""Tracing spans: per-request / per-step span trees with a ring-buffer
trace log and Chrome ``trace_event`` export.

``span("serve.step", bucket=str(b))`` opens a timed stage; nested
``span(...)`` calls in the same thread/context attach as children, so one
serving request or training step yields one tree covering its stages
(sample → pad → plan_cache → stamp → device_put → compile → execute; the
taxonomy table lives in ``docs/observability.md``). Completed **root**
spans land in a bounded ring buffer (old traces fall off; memory is
bounded by construction).

Context propagation uses :mod:`contextvars`: threads have independent
span stacks, so a prefetch producer's ``pipeline.produce`` tree never
interleaves with the consumer's ``serve.step`` tree — each thread's
roots enter the ring independently.

Export: :func:`chrome_trace` renders the ring as Chrome
``trace_event`` JSON ("X" complete events, µs timestamps relative to
process start) loadable in ``chrome://tracing`` / Perfetto;
:func:`write_chrome_trace` writes it to disk (also wired to
``REPRO_TRACE_PATH`` at process exit by :mod:`repro.obs`).

Profiler clock: once :mod:`jax` is imported, every span also opens a
``jax.profiler.TraceAnnotation`` of its name, so a ``jax.profiler``
trace shows the program's stages on the host plane, on the device
trace's clock, beside the ops they launched. The first such span also
registers the compile-phase listeners (:func:`repro.obs.hooks.
watch_compiles`). This module itself imports only the stdlib.

Disabled mode (``repro.obs.disable()``): ``span`` yields a shared no-op
span and records nothing — the per-call cost is one flag check.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

from repro.obs import registry as _registry

__all__ = ["Span", "span", "current_span", "spans", "reset_spans",
           "add_completed", "span_clock", "chrome_trace",
           "write_chrome_trace"]

_T0 = time.perf_counter()         # process-relative timestamp origin

_RING_CAP = int(os.environ.get("REPRO_TRACE_RING", "512"))
_RING: collections.deque = collections.deque(maxlen=_RING_CAP)
_RING_LOCK = threading.Lock()

_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_span", default=None)


class Span:
    """One timed stage. ``attrs`` carry structured context (bucket, step,
    cause, ...); ``children`` make the tree."""

    __slots__ = ("name", "attrs", "t0", "dur_s", "children", "tid",
                 "thread")

    def __init__(self, name: str, attrs: Dict):
        self.name = name
        self.attrs = attrs
        self.t0 = time.perf_counter() - _T0
        self.dur_s = 0.0
        self.children: List["Span"] = []
        self.tid = threading.get_ident()
        self.thread = threading.current_thread().name

    def set(self, **attrs) -> None:
        """Attach attributes mid-span (e.g. the bucket once known)."""
        self.attrs.update(attrs)

    # -- tree queries --------------------------------------------------------
    def find(self, name: str) -> Optional["Span"]:
        """First span named ``name`` in this subtree (depth-first)."""
        if self.name == name:
            return self
        for c in self.children:
            hit = c.find(name)
            if hit is not None:
                return hit
        return None

    def stages(self) -> set:
        """Every span name in this subtree."""
        out = {self.name}
        for c in self.children:
            out |= c.stages()
        return out

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()

    def as_dict(self) -> dict:
        return {"name": self.name, "t0_s": self.t0, "dur_s": self.dur_s,
                "attrs": dict(self.attrs), "thread": self.thread,
                "children": [c.as_dict() for c in self.children]}

    def __repr__(self):
        return (f"Span({self.name!r}, {self.dur_s * 1e3:.2f}ms, "
                f"{len(self.children)} children)")


class _NullSpan:
    """Shared no-op span for disabled mode."""

    name = None
    attrs: Dict = {}
    children: List = []
    dur_s = 0.0

    @property
    def t0(self) -> float:
        """Now, on the span clock: a no-op span starts and ends at once,
        so a caller that ties the span clock to its own still can."""
        return time.perf_counter() - _T0

    def set(self, **attrs) -> None:
        pass

    def find(self, name):
        return None

    def stages(self):
        return set()


_NULL = _NullSpan()


_ANNOTATION = None     # jax.profiler.TraceAnnotation, once jax is loaded


def _annotation():
    """``jax.profiler.TraceAnnotation`` once :mod:`jax` is in
    ``sys.modules`` (this module never imports it first), else None. The
    first time it is found, the compile-phase listeners go in too."""
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        import jax.profiler
        from repro.obs import hooks
        hooks.watch_compiles()
        _ANNOTATION = jax.profiler.TraceAnnotation
    return _ANNOTATION


@contextlib.contextmanager
def span(name: str, **attrs):
    """Open one timed stage; yields the live :class:`Span` (a shared
    no-op when observability is disabled). With :mod:`jax` loaded the
    stage is also a profiler annotation of the same name."""
    if not _registry._is_enabled():
        yield _NULL
        return
    annotation = _annotation()
    s = Span(name, attrs)
    parent = _CURRENT.get()
    token = _CURRENT.set(s)
    try:
        if annotation is None:
            yield s
        else:
            with annotation(name):
                yield s
    finally:
        s.dur_s = (time.perf_counter() - _T0) - s.t0
        _CURRENT.reset(token)
        if parent is not None:
            parent.children.append(s)
        else:
            with _RING_LOCK:
                _RING.append(s)


def add_completed(name: str, t0: float, dur_s: float,
                  **attrs) -> Optional[Span]:
    """Attach a stage that has already ended (``t0`` on the span clock,
    seconds since process start) as a child of the span open in this
    context; returns it, or None when no span is open. Children of that
    span that lie inside the new stage's interval move under it, so a
    stage reported after its nested stages (jax reports an outer trace
    after the traces it contains) still forms a tree."""
    parent = _CURRENT.get()
    if parent is None:
        return None
    s = Span(name, attrs)
    s.t0, s.dur_s = t0, dur_s
    # children are in order of their end; those nested in the new stage
    # ended last, so they are the tail that started after it
    kids = parent.children
    i = len(kids)
    while i and kids[i - 1].t0 >= t0:
        i -= 1
    s.children = kids[i:]
    del kids[i:]
    kids.append(s)
    return s


def span_clock(wall_s: float) -> float:
    """A ``time.time()`` reading on the span clock (seconds since the
    process-relative origin of :attr:`Span.t0`)."""
    return wall_s - time.time() + time.perf_counter() - _T0


def current_span() -> Optional[Span]:
    return _CURRENT.get()


def spans(name: Optional[str] = None) -> List[Span]:
    """Completed root spans in the ring (oldest first); ``name`` filters
    by root-span name."""
    with _RING_LOCK:
        roots = list(_RING)
    if name is not None:
        roots = [r for r in roots if r.name == name]
    return roots


def reset_spans() -> None:
    with _RING_LOCK:
        _RING.clear()


# ---------------------------------------------------------------------------
# Chrome trace_event export
# ---------------------------------------------------------------------------

def chrome_trace(roots: Optional[List[Span]] = None) -> dict:
    """The ring (or ``roots``) as a Chrome ``trace_event`` document:
    one "X" (complete) event per span, µs timestamps relative to process
    start, thread ids preserved so producer/consumer lanes separate."""
    if roots is None:
        roots = spans()
    events = []
    for root in roots:
        for s in root.walk():
            args = {k: (v if isinstance(v, (int, float, bool, str))
                        or v is None else str(v))
                    for k, v in s.attrs.items()}
            events.append({
                "name": s.name, "ph": "X", "cat": "repro",
                "ts": s.t0 * 1e6, "dur": s.dur_s * 1e6,
                "pid": os.getpid(), "tid": s.tid, "args": args,
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str,
                       roots: Optional[List[Span]] = None) -> str:
    doc = chrome_trace(roots)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path
