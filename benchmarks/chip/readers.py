"""What the per-layer metric files share: each file under ``metrics/`` is a
``read(ctx)`` that calls one of these with its own arguments.

``ctx`` holds the traced run's reduced trace (``summary``), the program's
spans in the window (``spans``), the chip's ``peaks`` and what the driver
counted (``window_s``, ``launch_groups``, ``plan``, ``flops``,
``span_units``). A reader that finds nothing to read returns
None, never 0.
"""
from __future__ import annotations

from benchmarks.chip import costs


def span_self_ms(ctx, names) -> float | None:
    """Self time of the named spans in the window, in ms per unit of work
    (a training step)."""
    units = ctx.get("span_units") or 0
    hits = [s["self_s"] for s in ctx.get("spans", ()) if s["name"] in names]
    if not hits or not units:
        return None
    return 1e3 * sum(hits) / units


def kernel_roofline(ctx, kernel: str) -> float | None:
    """Least time of the kernel's launches in the window over its device
    time there, in %."""
    least = sum(costs.least_time_by_kernel(launches, ctx["peaks"])
                .get(kernel, 0.0) * count
                for launches, count in ctx.get("launch_groups", ()))
    device_s = ctx["summary"].op_s.get(kernel, 0.0)
    if least <= 0 or device_s <= 0:
        return None
    return 100.0 * least / device_s


def idle_share(ctx) -> float | None:
    summary = ctx.get("summary")
    if summary is None or summary.window_s <= 0:
        return None
    return 100.0 * summary.idle_share


def mfu(ctx) -> float | None:
    """Required flops of the work done in the window, per second, over the
    chips' peak, in %."""
    flops, window = ctx.get("flops") or 0.0, ctx.get("window_s") or 0.0
    if flops <= 0 or window <= 0:
        return None
    return 100.0 * flops / window / (ctx["peaks"].flops_per_s
                                     * ctx["cell"].chips)
