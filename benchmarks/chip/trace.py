"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes. The
device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation the chip ran, named by its HLO instruction
(``%gather_segment_reduce.3 = f32[…] custom-call(…)``). Host annotations made with
``jax.profiler.TraceAnnotation`` sit on the host plane on the same clock.

* busy time: the union of the op intervals of a device inside the window;
  the idle share is 1 minus busy over the window;
* device time by stable name: an instruction's name without its ``.<n>``
  suffix, summed over its events;
* idle gaps: the complements of the busy union inside the window, each
  named by the host span that covered most of it.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def stable_name(op: str) -> str:
    """``%gather_segment_reduce.4 = f32[…] custom-call(…)`` (the TPU trace
    names an op by its HLO text) or ``fusion.12`` → the name without its
    ``.<n>`` suffix."""
    m = re.match(r"%?([^\s=]+)", op)
    return re.sub(r"\.\d+$", "", m.group(1) if m else op)


def merge(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(merged, lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(merged, lo, hi))


def gaps(merged, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class DeviceOps:
    """The op events of one device: ``(name, start_ns, end_ns)``."""
    device: int
    events: list


def xplane_file(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path):
    """(device op lists, host annotations) of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return read_data(ProfileData.from_file(str(path)))


def read_data(data):
    """(device op lists, host annotations ``{name: [(start, end)]}``) of a
    ``jax.profiler.ProfileData``."""
    devices, host = [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            events = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
            devices.append(DeviceOps(int(m.group(1)), events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                for e in line.events:
                    host.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    devices.sort(key=lambda d: d.device)
    return devices, host


@dataclasses.dataclass
class Summary:
    """What the per-layer readers and the result line take from a trace."""
    window_s: float
    busy_s: float                       # mean over the devices used
    op_s: dict                          # stable name -> seconds (all devices)
    gaps: list                          # (start_ns, end_ns), first device
    lo_ns: float
    hi_ns: float

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(devices, lo_ns: float, hi_ns: float,
              chips: int) -> Summary:
    """Reduce the first ``chips`` devices' ops to the window [lo, hi]."""
    used = devices[:chips]
    if len(used) < chips:
        raise ValueError(f"trace holds {len(devices)} devices, "
                         f"the cell uses {chips}")
    busy, op_s = [], {}
    for dev in used:
        spans = clip([(s, e) for _, s, e in dev.events], lo_ns, hi_ns)
        busy.append(covered(merge(spans), lo_ns, hi_ns))
        for name, s, e in dev.events:
            d = min(e, hi_ns) - max(s, lo_ns)
            if d > 0:
                key = stable_name(name)
                op_s[key] = op_s.get(key, 0.0) + d * 1e-9
    first = merge([(s, e) for _, s, e in used[0].events])
    return Summary(window_s=(hi_ns - lo_ns) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9, op_s=op_s,
                   gaps=gaps(first, lo_ns, hi_ns), lo_ns=lo_ns, hi_ns=hi_ns)


class HostClock:
    """Maps ``time.perf_counter()`` readings onto the trace's clock through
    one annotation whose perf_counter start was noted."""

    def __init__(self, perf_start: float, trace_start_ns: float):
        self.perf_start = perf_start
        self.trace_start_ns = trace_start_ns

    def to_ns(self, perf: float) -> float:
        return self.trace_start_ns + (perf - self.perf_start) * 1e9


def label_gaps(gap_list, spans, top: int = 10) -> list:
    """Idle seconds by the host span that covered each gap most, summed
    per span name: ``[[name, seconds], ...]``, longest first. ``spans`` is
    ``[(name, start_ns, end_ns)]``; of spans covering a gap alike the
    shorter (deeper) one wins, and a gap that no span covers is
    ``"host:outside_spans"``."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max((e - s for _, s, e in spans), default=0.0)
    totals: dict = {}
    for lo, hi in gap_list:
        best, best_key = "host:outside_spans", (0.0, 0.0)
        first = bisect.bisect_left(starts, lo - longest)
        for name, s, e in spans[first:bisect.bisect_right(starts, hi)]:
            key = (min(e, hi) - max(s, lo), -(e - s))
            if key[0] > 0 and key > best_key:
                best, best_key = name, key
        totals[best] = totals.get(best, 0.0) + (hi - lo) * 1e-9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in ranked]


def top_ops(op_s: dict, top: int = 10) -> list:
    ranked = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in ranked]
