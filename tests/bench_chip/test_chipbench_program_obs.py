"""The readers of what the program records in repro.obs over a run, set-up
included: nothing found gives None, hand-built spans and counters give the
right numbers, and on a tiny cell the grid steps the launches count agree
with the flat grid of the plan the step ran with."""
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, program_obs, tiny  # noqa: E402

METRICS = ("setup_plan_s.train", "setup_compile_s.train",
           "kernel_grid_useful_share.train")


def _span(name, t0, dur, *children):
    from repro.obs import Span
    s = Span(name, {})
    s.t0, s.dur_s = t0, dur
    s.children = list(children)
    return s


def _roots():
    """A first step as the trainer records it, and a later one."""
    first = _span(
        "train.step", 0.0, 10.0,
        _span("train.prepare", 0.0, 1.5,
              _span("plan.build", 0.0, 1.0,
                    _span("jax.compile", 0.1, 0.5)),
              _span("plan.build", 1.0, 0.25)),
        _span("train.compile", 1.5, 8.0,
              _span("jax.trace", 1.5, 2.0, _span("jax.trace", 2.0, 0.5)),
              _span("jax.lower", 3.5, 1.0),
              _span("jax.compile", 4.5, 4.0)),
        _span("train.sync", 9.5, 0.5))
    later = _span("train.step", 10.0, 3.0,
                  _span("train.execute", 10.0, 0.1),
                  _span("train.sync", 10.1, 2.9))
    return [first, later]


def _registry(grid):
    from repro.obs.registry import MetricsRegistry
    reg = MetricsRegistry()
    c = reg.counter("kernel.grid_steps", ("op", "kind"))
    for op, walked, owned in grid:
        c.inc(walked, op=op, kind="walked")
        c.inc(owned, op=op, kind="owned")
    return reg


def test_readers_find_nothing_without_their_inputs():
    from repro import obs
    from repro.obs.registry import MetricsRegistry
    assert program_obs.span_seconds("plan.build", []) is None
    assert program_obs.compile_seconds("train.compile", []) is None
    later = _roots()[1:]
    assert program_obs.span_seconds("plan.build", later) is None
    assert program_obs.compile_seconds("train.compile", later) is None
    assert program_obs.grid_useful_share(MetricsRegistry()) is None
    assert program_obs.grid_useful_share(_registry([])) is None
    obs.reset()
    for name in METRICS:
        assert harness.load_metric(name)({}) is None


def test_window_spans_read_nothing_with_observability_off():
    from repro import obs
    obs.disable()
    try:
        assert harness.program_spans(0.0, time.perf_counter()) == []
    finally:
        obs.enable()


def test_readers_on_hand_built_spans_and_counters():
    roots = _roots()
    assert program_obs.span_seconds("plan.build", roots) == 1.25
    # the nested trace counts once, inside the outer one; plan.build's
    # eager compile is not the step's
    assert program_obs.compile_seconds("train.compile", roots) == 7.0
    reg = _registry([("gather_segment_reduce", 600.0, 3.0),
                     ("fused_transform_reduce", 400.0, 2.0)])
    assert program_obs.grid_useful_share(reg) == pytest.approx(0.5)
    assert program_obs.counter_by_label("kernel.grid_steps", "op", reg) == {
        "gather_segment_reduce": 603.0, "fused_transform_reduce": 402.0}


def test_metric_files_read_the_program_registry_and_ring():
    from repro import obs
    obs.enable()
    obs.reset()
    with obs.span("train.step"):
        with obs.span("train.prepare"):
            with obs.span("plan.build"):
                pass
    grid = obs.get_registry().counter("kernel.grid_steps", ("op", "kind"))
    grid.inc(8.0, op="gather_segment_reduce", kind="walked")
    grid.inc(2.0, op="gather_segment_reduce", kind="owned")
    assert harness.load_metric("setup_plan_s.train")({}) > 0
    assert harness.load_metric("kernel_grid_useful_share.train")({}) == 25.0
    assert harness.load_metric("setup_compile_s.train")({}) is None
    obs.reset()


def _grid_by_op(kind: str) -> dict:
    from repro import obs
    out: dict = {}
    for labels, cell in obs.get_registry().get(
            "kernel.grid_steps").series_items():
        if labels["kind"] == kind:
            out[labels["op"]] = out.get(labels["op"], 0.0) + cell[0]
    return out


def test_grid_share_counted_by_the_launches_matches_the_plan(tmp_path):
    # every executed step adds, for each launch of the gather and fused
    # kernels, the flat grid's steps for the plan the step ran with as
    # walked, and the plan's chunks as owned
    from repro import obs
    from repro.kernels.segment_reduce import flat_grid_steps
    from benchmarks.chip.drivers.train import CHECKED_STEPS
    obs.enable()
    obs.reset()
    name = "gcn-arxiv.train-powerlaw"
    cell = tiny.cell(name, tmp_path, nodes=1024, edges=8192)
    with tiny.isolated_jax_config(tmp_path / "jax_cache"):
        out = harness.load_driver(cell.traffic).run(
            cell, seed=2**33 + 5, seconds=0.1, tracer=harness.Tracer(False),
            t0=time.perf_counter())
    plan = out.layer["plan"]
    flat = flat_grid_steps(plan["input_chunks"], plan["out_blocks"],
                           plan["max_chunks"])
    assert flat < plan["out_blocks"] * plan["max_chunks"]
    executed = CHECKED_STEPS + out.attempted
    [(launches, _)] = out.layer["launch_groups"]
    walked, owned = _grid_by_op("walked"), _grid_by_op("owned")
    for kernel in ("gather_segment_reduce", "fused_transform_reduce"):
        count = sum(launch.kernel == kernel for launch in launches)
        ops = [op for op in walked if op.startswith(kernel)]
        assert count >= 1 and ops
        got = sum(walked[op] for op in ops)
        assert got == executed * count * flat
        assert sum(owned[op] for op in ops) == \
            executed * count * plan["chunks"] <= got
    assert program_obs.compile_seconds("train.compile") > 0
    assert program_obs.span_seconds("plan.build") > 0
