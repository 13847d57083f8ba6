"""Readers of what the program itself recorded in ``repro.obs`` over the
whole run, set-up included: its span trees (``repro.obs.spans()``; the
harness keeps up to ``REPRO_TRACE_RING`` roots) and its metrics registry.
The per-layer metrics of set-up and of the kernels' grid read these, as
set-up lies outside the window that ``ctx["spans"]`` covers.

A program that records none of it (an older one, or one run with
``REPRO_OBS=0``) gives None, never 0.
"""
from __future__ import annotations

COMPILE_PHASES = ("jax.trace", "jax.lower", "jax.compile")


def _walk(roots):
    for root in roots:
        yield from root.walk()


def _roots():
    from repro import obs
    return obs.spans()


def span_seconds(name: str, roots=None) -> float | None:
    """Summed duration of every span named ``name`` in the run."""
    hits = [s.dur_s for s in _walk(_roots() if roots is None else roots)
            if s.name == name]
    return sum(hits) if hits else None


def compile_seconds(under: str, roots=None) -> float | None:
    """Summed duration of the compile phases (``jax.trace``,
    ``jax.lower``, ``jax.compile``) that descend from a span named
    ``under``. A phase nested in another (a trace inside a trace) counts
    once, inside the outer one."""
    total, found = 0.0, False

    def outermost(span):
        nonlocal total, found
        if span.name in COMPILE_PHASES:
            total += span.dur_s
            found = True
            return
        for child in span.children:
            outermost(child)

    for span in _walk(_roots() if roots is None else roots):
        if span.name == under:
            for child in span.children:
                outermost(child)
    return total if found else None


def counter_by_label(name: str, label: str, registry=None) -> dict:
    """``{label value: summed value}`` of a counter over its series, or
    {} where the program has no such counter."""
    if registry is None:
        from repro import obs
        registry = obs.get_registry()
    metric = registry.get(name)
    if metric is None:
        return {}
    out: dict = {}
    for labels, cell in metric.series_items():
        out[labels[label]] = out.get(labels[label], 0.0) + cell[0]
    return out


def grid_useful_share(registry=None) -> float | None:
    """100 × Σ owned / Σ walked of ``kernel.grid_steps`` over the run, in
    %: the share of the kernels' (output block, chunk) grid steps that
    had work."""
    kinds = counter_by_label("kernel.grid_steps", "kind", registry)
    walked = kinds.get("walked", 0.0)
    if walked <= 0:
        return None
    return 100.0 * kinds.get("owned", 0.0) / walked
