"""Full-graph training through the program's trainer (what ``repro.fit``
runs), one graph for the whole run.

Set-up builds the graph and the weights from the seed, one ``Trainer`` with
its state, and drives it through the first three steps: the first compiles
(or loads from the compile cache), and the three are what the comparison
checks. The window then calls the same trainer, one step per
``Trainer.fit`` call, until ``seconds`` have passed; each step ends when the
trainer reads its loss back. After the window the program's state is
dropped and the cell's plain reference (``cell.model``, found by the
configuration's ``reference``) runs the same three steps.

The configuration's optional ``model_options`` reach the program's model
as keywords, in :func:`build` and :func:`program.to_params`.

Traffic keys: ``driver`` ("train") and ``law`` (see :func:`graphs.make_graph`).
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import compare, costs, graphs, program
from benchmarks.chip.harness import Outcome, program_spans

CHECKED_STEPS = 3


class OneGraph:
    """A provider whose every batch is the same graph."""

    def __init__(self, graph):
        self.graph = graph

    def batch(self, step: int):
        return self.graph


def build(cfg: dict, graph, params):
    """The program's trainer for ``cfg`` over ``graph``, and its state from
    the benchmark's ``params``."""
    from repro import NodeClassification, Trainer, TrainerConfig
    from repro.optim import adamw
    from repro.train.trainer import TrainState
    task = NodeClassification(model=cfg["model"], d_in=cfg["num_features"],
                              hidden=cfg["hidden_channels"],
                              num_classes=cfg["num_classes"],
                              num_layers=cfg["num_layers"], impl="pallas",
                              **cfg.get("model_options", {}))
    # Adam as the configuration states it: no weight decay, no clipping
    opt = adamw.AdamWConfig(lr=cfg["lr"], b1=cfg["adam_b1"],
                            b2=cfg["adam_b2"], eps=cfg["adam_eps"],
                            weight_decay=0.0, grad_clip=math.inf)
    trainer = Trainer(task, OneGraph(graph),
                      TrainerConfig(steps=1, opt=opt, warmup_steps=0,
                                    lr_schedule="constant"),
                      tune=False)
    tree = program.to_params(params, cfg)
    state = TrainState(tree, adamw.init(tree, opt), jnp.zeros((), jnp.int32),
                       jax.random.PRNGKey(0))
    return trainer, state


def plan_counts(trainer, graph) -> dict:
    """Chunks owned (Σ chunk_count), output blocks, ``max_chunks`` and input
    chunks (``worst_case_chunks``) of the plan the step runs with, as the
    trainer's task prepares it (memoized, so the same one)."""
    arrays, _ = trainer.task.prepare(graph, plan=trainer.plan,
                                     config=trainer.config, tune=trainer.tune)
    plan = arrays["plan"]
    counts = np.asarray(plan.chunk_count)
    return {"chunks": int(counts.sum()), "out_blocks": int(counts.size),
            "max_chunks": int(plan.max_chunks),
            "input_chunks": int(plan.worst_case_chunks)}


class GcPauses:
    """Collections of the garbage collector while installed, with their
    generation and length: a host pause that a step time may show."""

    def __init__(self):
        self.pauses, self._start = [], None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.pauses.append((info["generation"],
                                round(time.perf_counter() - self._start, 6)))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def step_parts(lo: float, hi: float) -> dict:
    """Per window step, the seconds the program spent calling the step
    (``train.execute``) and in ``train.step``'s own time (reading the loss
    back, mostly), from its spans."""
    parts: dict = {"train.execute": [], "train.step": []}
    for sp in program_spans(lo, hi):
        if sp["name"] in parts:
            parts[sp["name"]].append(round(
                sp["end"] - sp["start"] if sp["name"] == "train.execute"
                else sp["self_s"], 6))
    return parts


def run(cell, *, seed: int, seconds: float, tracer, t0: float) -> Outcome:
    cfg, traffic = cell.config, cell.traffic
    g = graphs.make_graph(traffic["law"], cfg["num_nodes"],
                          cfg["num_edges"], cfg["num_features"],
                          cfg["num_classes"], graphs.rng_for(seed, "graph"))
    t_graph = time.perf_counter()
    params = cell.model.init_params(cfg, seed)
    p0 = [{k: np.asarray(a) for k, a in layer.items()} for layer in params]
    pg = program.to_graph(g, f"{cell.name}-{seed}")
    trainer, state = build(cfg, pg, params)

    recorder = costs.LaunchRecorder()
    losses, mu1, step_s = [], None, []
    with recorder.installed():
        for i in range(CHECKED_STEPS):
            t = time.perf_counter()
            res = trainer.fit(state=state)
            step_s.append(time.perf_counter() - t)
            state = res.state
            losses.append(res.losses[-1])
            if i == 0:
                mu1 = program.from_tree(state.opt_state.mu)
    launches = recorder.take()
    b1 = cfg["adam_b1"]
    got = {"losses": losses,
           "grad_norms": {k: v / (1.0 - b1) for k, v in
                          compare.leaf_norms(mu1).items()},
           "delta_norms": compare.leaf_norms(
               [{k: a - p0[i][k] for k, a in layer.items()}
                for i, layer in enumerate(program.from_tree(state.params))])}
    plan = plan_counts(trainer, pg)
    traces_before = trainer.traces

    nonfinite, ends = 0, []
    setup_s = time.perf_counter() - t0
    with tracer.window(), GcPauses() as collections:
        t_start = time.perf_counter()
        while True:
            with tracer.annotate("bench.trainer_fit"):
                res = trainer.fit(state=state)
            state = res.state
            nonfinite += not math.isfinite(res.losses[-1])
            ends.append(time.perf_counter())
            if ends[-1] - t_start >= seconds:
                break
    steps, window_s = len(ends), ends[-1] - t_start
    retraces = trainer.traces - traces_before
    parts = step_parts(t_start, ends[-1])

    peak = program.memory_peak_bytes()
    del trainer, state, res
    gc.collect()
    ref = cell.model.train(cfg, g, seed, steps=CHECKED_STEPS)
    numbers = compare.train_numbers(got, ref)

    notes = [
        f"[setup] graph V={g.num_nodes} E={g.num_edges} "
        f"law={traffic['law']['kind']} made in {t_graph - t0:.3f} s "
        f"(from process start)",
        f"[setup] first steps s={[round(s, 6) for s in step_s]} "
        f"(the first includes compiling or loading the step); "
        f"setup_s={setup_s:.6f}",
        f"[setup] kernel launches per step: "
        f"{[(l.kernel, l.num_edges, l.d_in, l.d_out) for l in launches]}",
        f"[setup] plan {plan}",
        f"[window] steps={steps} window_s={window_s:.6f} "
        f"retraces={retraces} nonfinite={nonfinite}",
        f"[window] step_s="
        f"{[round(b - a, 6) for a, b in zip([t_start] + ends, ends)]}",
        f"[window] execute_s={parts['train.execute']} "
        f"step_self_s={parts['train.step']} gc={collections.pauses}",
        f"[check] losses={losses} ref={ref['losses']}",
    ]
    flops = cell.model.train_step_flops(cfg, g.num_nodes, g.num_edges)
    return Outcome(
        attempted=steps, failed=nonfinite,
        end_to_end={"train_step_s": window_s / steps, "setup_s": setup_s},
        numbers=numbers, memory_peak_bytes=peak,
        layer={"window_s": window_s, "launch_groups": [(launches, steps)],
               "plan": plan, "flops": flops * steps, "span_units": steps},
        notes=notes)
