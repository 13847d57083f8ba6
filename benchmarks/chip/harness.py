"""Finds a cell's pieces by name and runs the cell once.

``BENCHMARK.json`` at the root of the checkout names the cells. For a cell
``<config>.<traffic>`` the harness reads:

* the configuration file that ``configs[*].file`` names. Its
  ``reference`` key names the cell's model, ``references/<reference>.py``:
  the plain reference with ``init_params(cfg, seed)``, ``train(cfg, graph,
  seed, *, steps, precision="highest", rows=None)`` (what
  :func:`compare.train_numbers` takes) and ``train_step_flops(cfg, nodes,
  edges)``. An optional ``model_options`` object goes to the program's
  model as keywords (``heads`` for GAT);
* ``traffic/<traffic>.json`` — parameters for the generators, and the
  ``driver`` (``drivers/<driver>.py``) that drives the program's entry
  point with them;
* ``limits/<cell>.json`` — the limit of each number compared;
* ``metrics/<metric>.py`` for each per-layer metric the cell reports: a
  ``read(ctx)`` that returns a number, or None where there is nothing to
  read (the metric is then left out of the line).

A new cell, configuration, model, traffic mix or metric is a new file and
a new entry; no file here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import time
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = ROOT / ".bench_chip" / "trace"


class CellError(Exception):
    """The cell cannot run as BENCHMARK.json and its files describe it."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    model: types.ModuleType   # references/<reference>.py
    traffic: dict
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list
    limits: dict


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str, e2e_names=None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def find_cell(bench: dict, name: str, *, root: Path = ROOT,
              here: Path = HERE) -> Cell:
    from benchmarks.chip import compare
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    if "reference" not in config:
        raise CellError(f"configuration {w['config']!r} names no reference")
    traffic = json.loads((here / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                model=load_model(config["reference"], here),
                traffic=traffic, end_to_end=e2e, per_layer=layer,
                limits=compare.load_limits(name, here / "limits"))


def load_driver(traffic: dict):
    return importlib.import_module(
        f"benchmarks.chip.drivers.{traffic['driver']}")


def _load_file(kind: str, name: str, path: Path):
    mod_name = f"benchmarks_chip_{kind}_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.exists():
        raise CellError(f"no {kind} {name!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_metric(name: str, here: Path = HERE):
    """The ``read`` function of ``metrics/<name>.py``."""
    return _load_file("metric", name, here / "metrics" / f"{name}.py").read


MODEL_FUNCTIONS = ("init_params", "train", "train_step_flops")


def load_model(name: str, here: Path = HERE):
    """The module ``references/<name>.py``: a model's plain reference and
    its flops, as :data:`MODEL_FUNCTIONS` name them."""
    module = _load_file("reference", name,
                        here / "references" / f"{name}.py")
    missing = [f for f in MODEL_FUNCTIONS if not callable(getattr(module,
                                                                  f, None))]
    if missing:
        raise CellError(f"reference {name!r} at {module.__file__} lacks "
                        f"{missing}")
    return module


# ---------------------------------------------------------------------------
# the window and its trace
# ---------------------------------------------------------------------------

class Tracer:
    """Opens the measured window; with ``enabled`` it also profiles it.

    ``window()`` brackets the window with a ``bench.window`` annotation and
    notes ``time.perf_counter()`` at its start, which ties the program's
    host spans to the trace's clock. ``annotate(name)`` marks a call into
    a layer from the driver."""

    def __init__(self, enabled: bool, directory: Path = TRACE_DIR):
        self.enabled = enabled
        self.directory = directory
        self.perf_start = self.perf_end = None

    @contextlib.contextmanager
    def window(self):
        import jax
        if self.enabled:
            shutil.rmtree(self.directory, ignore_errors=True)
            self.directory.mkdir(parents=True)
            # host annotations (TraceMe) and device ops; no Python call
            # tracing, which would slow the host path it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.directory),
                                     profiler_options=opts)
        try:
            with self.annotate("bench.window"):
                self.perf_start = time.perf_counter()
                yield self
                self.perf_end = time.perf_counter()
        finally:
            if self.enabled:
                jax.profiler.stop_trace()

    def annotate(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def reduce(self, chips: int):
        """(trace Summary, HostClock) of the window just traced."""
        from benchmarks.chip import trace
        devices, host = trace.read(trace.xplane_file(self.directory))
        marks = host.get("bench.window")
        if not marks:
            raise CellError("the trace holds no bench.window annotation")
        lo, hi = marks[-1]
        summary = trace.summarize(devices, lo, hi, chips)
        return summary, trace.HostClock(self.perf_start, lo)

    def clean(self) -> None:
        shutil.rmtree(self.directory, ignore_errors=True)


@dataclasses.dataclass
class Outcome:
    """What a driver hands back after its window and its comparison."""
    attempted: int
    failed: int
    end_to_end: dict          # name -> value
    numbers: dict             # compared number -> value
    memory_peak_bytes: int
    layer: dict               # what the per-layer readers read
    notes: list               # earlier lines of the run


def _device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def program_spans(lo_perf: float, hi_perf: float) -> list:
    """Every span of the program's span trees whose root overlaps
    [lo, hi] on ``time.perf_counter()``'s clock: dicts with ``name``,
    ``start``, ``end`` and ``self_s`` (its time less its children's)."""
    from repro import obs
    with obs.span("bench.sync") as mark:
        offset = time.perf_counter() - mark.t0
    out = []
    for root in obs.spans():
        if root.name == "bench.sync":
            continue
        start = root.t0 + offset
        if start + root.dur_s < lo_perf or start > hi_perf:
            continue
        for sp in root.walk():
            begin = sp.t0 + offset
            out.append({"name": sp.name, "start": begin,
                        "end": begin + sp.dur_s,
                        "self_s": sp.dur_s - sum(c.dur_s
                                                 for c in sp.children)})
    return out


def run(args, t0: float) -> int:
    """One run of one cell; returns the exit code."""
    try:
        cell = find_cell(load_benchmark(), args.workload)
    except (CellError, FileNotFoundError, KeyError) as e:
        print(f"run: {e}", file=sys.stderr)
        return 1

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"run: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"run: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    return execute(cell, args, t0)


def configure_jax(config: dict) -> str:
    """Compile cache in the checkout, and the configuration's precision."""
    import jax
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # every program, however quick to compile, is kept, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    return cache


def execute(cell: Cell, args, t0: float, *, log=None) -> int:
    """Drive the cell (no look for a chip: the tests call this on the CPU)
    and print its result line."""
    from benchmarks.chip import compare

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cache = configure_jax(cell.config)
    log(f"[setup] cell={cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} compile_cache={cache} "
        f"matmul_precision={cell.config['matmul_precision']}")
    tracer = Tracer(bool(args.trace))
    driver = load_driver(cell.traffic)
    out = driver.run(cell, seed=args.seed, seconds=args.seconds,
                     tracer=tracer, t0=t0)
    for note in out.notes:
        log(note)
    gc.collect()

    device = _device_info(cell.chips)
    device["memory_peak_bytes"] = out.memory_peak_bytes
    result = {"correct": None, "attempted": out.attempted,
              "failed": out.failed, "metrics": {}, "device": device}
    if args.trace:
        summary, clock = tracer.reduce(cell.chips)
        tracer.clean()
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        from benchmarks.chip.peaks import peaks_for
        spans = program_spans(tracer.perf_start, tracer.perf_end)
        ctx = dict(out.layer, cell=cell, summary=summary, spans=spans,
                   peaks=peaks_for(device["kind"]))
        for m in cell.per_layer:
            value = load_metric(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        from benchmarks.chip import trace
        result["breakdown"] = {
            "device_ops": trace.top_ops(summary.op_s),
            "idle_gaps": trace.label_gaps(
                summary.gaps, [(sp["name"], clock.to_ns(sp["start"]),
                                clock.to_ns(sp["end"])) for sp in spans])}
    else:
        for m in cell.end_to_end:
            if m["name"] not in out.end_to_end:
                raise CellError(f"driver gave no {m['name']}")
            result["metrics"][m["name"]] = {"value": out.end_to_end[m["name"]],
                                            "unit": m["unit"]}

    ok, checks = compare.judge(out.numbers, cell.limits)
    result["correct"] = bool(ok and out.failed == 0)
    result["checks"] = dict(checks, failed={"value": out.failed, "limit": 0})
    for name, c in result["checks"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(_finite(result)), flush=True)
    return 0


def _finite(x):
    """The result with every non-finite number as null: JSON has none."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def main(argv=None, t0=None) -> int:
    import argparse
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("REPRO_TRACE_RING", "1000000")
    return run(args, t0)
