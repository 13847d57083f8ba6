"""Set-up phases, the loss wait and each launch's grid walk in repro.obs:
the trainer's span tree with plan.build, the jax compile phases and
train.sync; kernel.grid_steps per executed step; nothing recorded and no
profiler annotation opened while observability is off; the spans on a
jax.profiler trace's host plane."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import repro
from repro import obs
from repro.core.config_space import KernelConfig
from repro.obs import trace as obs_trace

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import trace as chip_trace  # noqa: E402


@pytest.fixture(autouse=True)
def clean_obs():
    obs.enable()
    obs.reset()
    yield
    obs.enable()


def _trainer(nodes=32, edges=96, config=None, steps=1):
    data = repro.GraphEpochProvider(shapes=((nodes, edges),),
                                    graphs_per_shape=1, feat=8,
                                    num_classes=4)
    task = repro.NodeClassification.from_provider(data, model="gcn",
                                                  hidden=8)
    return repro.Trainer(task, data, repro.TrainerConfig(steps=steps),
                         config=config)


def _children(span):
    return [c.name for c in span.children]


def test_first_step_tree_holds_plan_build_compile_phases_and_sync():
    _trainer().fit()
    (root,) = obs.spans("train.step")
    assert _children(root) == ["train.sample", "train.prepare",
                               "train.compile", "train.sync"]
    prepare = root.find("train.prepare")
    builds = [c for c in prepare.children if c.name == "plan.build"]
    assert builds
    for b in builds:
        assert {"num_edges", "out_blocks", "chunks_owned", "max_chunks",
                "worst_case_chunks", "config"} <= set(b.attrs)
        assert b.attrs["num_edges"] == 96
        assert b.attrs["max_chunks"] <= b.attrs["worst_case_chunks"]
    compile_ = root.find("train.compile")
    assert {"jax.trace", "jax.lower", "jax.compile"} <= \
        set(_children(compile_))
    (backend,) = [c for c in compile_.children if c.name == "jax.compile"]
    assert backend.attrs["cache"] in ("hit", "miss")
    assert backend.attrs["fun_name"] == "jit(step)"
    # each phase lies inside the span it was attached to
    for c in compile_.children:
        assert compile_.t0 - 1e-3 <= c.t0
        assert c.t0 + c.dur_s <= compile_.t0 + compile_.dur_s + 1e-3
    reg = obs.get_registry()
    phases = reg.get("compile.phase_s")
    assert all(phases.count(phase=p) >= 1
               for p in ("trace", "lower", "compile"))


def test_a_cached_step_has_no_compile_phases_and_no_plan_build():
    trainer = _trainer()
    trainer.fit()
    obs.reset_spans()
    trainer.fit()
    (root,) = obs.spans("train.step")
    assert _children(root) == ["train.sample", "train.prepare",
                               "train.execute", "train.sync"]
    assert not [s for s in root.walk()
                if s.name == "plan.build" or s.name.startswith("jax.")]


def test_nested_traces_form_a_tree_under_the_outer_trace():
    _trainer().fit()
    compile_ = obs.spans("train.step")[0].find("train.compile")
    (outer,) = [c for c in compile_.children if c.name == "jax.trace"]
    assert outer.attrs["fun_name"] == "step"
    # the kernels' jitted wrappers trace inside the step's trace
    assert any(c.name == "jax.trace" for c in outer.children)


def _walked(op, plan):
    """Grid steps one launch of ``op`` walks: the gather and fused kernels
    walk the flat grid of owned (block, chunk) steps, the others every
    (block, chunk) pair up to ``max_chunks``."""
    out_blocks = int(plan.chunk_count.shape[0])
    if op.startswith(("gather_segment_reduce", "fused_transform_reduce")):
        return min(plan.worst_case_chunks + out_blocks - 1,
                   out_blocks * plan.max_chunks)
    return out_blocks * plan.max_chunks


def test_grid_steps_count_each_executed_launch():
    # a plan with several output blocks and chunks, pinned to the worst
    # case as the trainer pins it
    cfg = KernelConfig(schedule="SR", s_b=128, n_b=128, m_b=128)
    trainer = _trainer(nodes=256, edges=1024, config=cfg)
    trainer.fit()
    trainer.fit()                                   # a second executed step
    g = trainer.data.batch(0)
    arrays, static = trainer.task.prepare(g, config=cfg)
    plan = arrays["plan"]
    owned = int(np.asarray(plan.chunk_count).sum())
    assert owned < int(plan.chunk_count.shape[0]) * plan.max_chunks
    assert trainer.task.chunks_owned(arrays) == owned
    manifest = trainer._grids[static]
    grid = obs.get_registry().get("kernel.grid_steps")
    assert manifest
    assert any(op.startswith("gather_segment_reduce") for op in manifest)
    for op, (launches, _) in manifest.items():
        walked = _walked(op, plan)
        assert owned <= walked
        assert grid.value(op=op, kind="walked") == 2 * launches * walked
        assert grid.value(op=op, kind="owned") == 2 * launches * owned


def test_pinned_plan_walks_a_grid_the_plan_owns():
    # thirty-two chunks over two output blocks: the pinned (block, chunk)
    # grid would walk 64 steps a launch, the flat grid at most 33
    cfg = KernelConfig(schedule="SR", s_b=128, n_b=128, m_b=128)
    _trainer(nodes=256, edges=4096, config=cfg).fit()
    from benchmarks.chip.program_obs import counter_by_label
    grid = obs.get_registry().get("kernel.grid_steps")
    flat = [op for op in counter_by_label("kernel.grid_steps", "op")
            if op.startswith(("gather_segment_reduce",
                              "fused_transform_reduce"))]
    assert flat
    owned = sum(grid.value(op=op, kind="owned") for op in flat)
    walked = sum(grid.value(op=op, kind="walked") for op in flat)
    assert owned >= 0.9 * walked


def test_disabled_records_nothing_and_opens_no_annotation(monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name, **kw):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    obs_trace._annotation()                  # jax is loaded: resolve it
    monkeypatch.setattr(obs_trace, "_ANNOTATION", Annotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Annotation)
    obs.disable()
    _trainer().fit()
    assert opened == []
    assert obs.spans() == []
    reg = obs.get_registry()
    for name in ("kernel.grid_steps", "compile.phase_s", "compile.cache"):
        metric = reg.get(name)
        assert metric is None or metric.series_items() == []
    # the same run with observability on opens them
    obs.enable()
    _trainer().fit()
    assert {"train", "train.step", "train.prepare", "plan.build",
            "train.compile", "train.sync"} <= set(opened)


def test_spans_land_on_the_profile_host_plane(tmp_path):
    trainer = _trainer()
    trainer.fit()
    jax.profiler.start_trace(str(tmp_path))
    try:
        trainer.fit()
    finally:
        jax.profiler.stop_trace()
    _, host = chip_trace.read(chip_trace.xplane_file(tmp_path))
    assert {"train.step", "train.sample", "train.prepare", "train.execute",
            "train.sync"} <= set(host)
    # the step marker the profiler's step view reads
    assert "train" in host
    (step,) = host["train.step"]
    (sync,) = host["train.sync"]
    assert step[0] <= sync[0] and sync[1] <= step[1]


def test_disabled_span_still_reads_the_span_clock():
    import time
    obs.disable()
    with obs.span("bench.sync") as mark:
        offset = time.perf_counter() - mark.t0
    assert mark.dur_s == 0.0
    assert offset == pytest.approx(obs_trace._T0, abs=1e-3)
    assert obs.spans() == []


def test_completed_stage_without_an_open_span_is_dropped():
    assert obs_trace.add_completed("jax.compile", 0.0, 1.0) is None
    assert obs.spans() == []
