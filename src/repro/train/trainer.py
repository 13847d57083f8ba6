"""The ``Trainer`` leg of the orchestration protocol — assembles the
optimizer (:mod:`repro.optim.adamw` + :mod:`repro.optim.schedule`), one
**jitted plan-reusing train step per shape bucket**, periodic checkpointing
with resume, and the fault-tolerance machinery
(:class:`~repro.distributed.fault_tolerance.ResilientLoop` +
``StragglerMonitor`` / ``StepWatchdog``) around the loop.

Compile discipline — the property the whole library exists for: the task's
``prepare`` maps each batch to a hashable *static signature* (its shape
bucket); the trainer jits exactly one step executable per signature, and
the batch's :class:`~repro.core.plan.SegmentPlan` rides into it **as a
pytree argument** — chunk-metadata leaves vary per graph, the static aux
(kernel config, grid bound) is part of the treedef — so re-invocation on
the same bucket never retraces. A trace-time side-effect counter
(``Trainer.traces``) audits it: after any number of steps,
``traces == len(buckets)``.

Resume semantics: :class:`TrainState` (params + optimizer state + step +
PRNG key) is the unit of checkpointing. ``fit(resume=True)`` restores the
latest complete checkpoint in ``ckpt_dir`` and continues from its step;
because providers are deterministic in the step index and the PRNG key is
part of the state, the resumed loss trajectory is bit-identical to an
uninterrupted run (tests/test_train.py locks this).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.checkpoint import checkpoint as ckpt
from repro.obs import span
from repro.distributed.fault_tolerance import (ResilientLoop,
                                               ResilientLoopConfig)
from repro.kernels import ops as kops
from repro.optim import adamw, schedule

__all__ = ["TrainState", "TrainerConfig", "FitResult", "Trainer", "fit"]


class TrainState(NamedTuple):
    """Everything a resumed run needs — one checkpointable pytree."""
    params: Any
    opt_state: adamw.AdamWState
    step: jax.Array               # () int32 — the *next* step to run
    rng: jax.Array                # PRNG key; folded with step per step


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Loop + optimizer + fault-tolerance knobs (one frozen config)."""
    steps: int = 100
    opt: adamw.AdamWConfig = adamw.AdamWConfig()
    warmup_steps: int = 10
    lr_schedule: str = "warmup_cosine"    # see repro.optim.schedule.get
    seed: int = 0
    # checkpointing (None ⇒ no checkpoints, no resume)
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep: int = 3
    # fault tolerance (threaded into ResilientLoopConfig)
    max_restarts: int = 3
    step_timeout_s: Optional[float] = None
    straggler_factor: float = 3.0
    log_every: int = 0


class FitResult(NamedTuple):
    state: TrainState
    losses: list                  # per-step losses, in step order
    start_step: int               # first step this fit actually ran
    traces: int                   # train-step traces (compiles) so far
    buckets: tuple                # static signatures seen (one exe each)
    events: tuple                 # ResilientLoop event log


class Trainer:
    """``Trainer(task, data, cfg).fit()`` — see the module docstring.

    ``task``: the :class:`~repro.train.task.Task` protocol — ``init(rng)``,
    ``prepare(batch, *, plan, config, tune, mesh)`` →
    ``(arrays, static)``, and ``loss(params, arrays, static, rng, mesh=)``
    → ``(loss, metrics)``. A task may also offer ``build_step(trainer_cfg,
    mesh, static)`` returning a ready ``(state, arrays) -> (state,
    metrics)`` callable (or None to use the generic step) — the hook that
    revives :mod:`repro.distributed.step`'s build-step pattern for tasks
    with their own sharded step (the LM pjit path).

    ``(plan=, config=, tune=)`` follow the library-wide precedence
    (``docs/plans.md``): an explicit ``plan=`` is authoritative for every
    batch (single-shape data), else ``config=`` pins the kernel config the
    per-graph planning selects, else ``tune=`` engages the measured
    autotuner tier, else the generated rules decide. ``mesh=`` (a 1-D
    device mesh) reroutes graph aggregations through
    :mod:`repro.core.dist_mp` — the task partitions each batch and the
    same fused kernels run per shard.
    """

    def __init__(self, task, data, cfg: Optional[TrainerConfig] = None, *,
                 mesh=None, plan=None, config=None, tune=None):
        self.task = task
        self.data = data
        self.cfg = cfg if cfg is not None else TrainerConfig()
        self.mesh = mesh
        self.plan = plan
        self.config = config
        self.tune = tune
        self._execs: dict = {}        # static signature -> jitted step
        # telemetry: per-trainer accounting in the repro.obs registry
        # (vital — `traces` works with observability disabled)
        reg = obs.get_registry()
        self._labels = {"trainer": obs.next_id("trainer")}
        self._m_steps = reg.counter("train.steps", ("trainer",), vital=True)
        self._m_traces = reg.counter("train.traces", ("trainer",),
                                     vital=True)
        self._m_steps.touch(**self._labels)
        self._m_traces.touch(**self._labels)
        self._m_grid = reg.counter(
            "kernel.grid_steps", ("op", "kind"),
            help="(output block, chunk) kernel grid steps per executed "
                 "launch: walked, and owned by the plan's chunk counts")
        self._traced_statics: set = set()   # signatures already compiled
        self._grids: dict = {}   # signature -> launch manifest of its trace

    def _note_trace(self, static) -> None:
        """Trace-time side effect: fires once per compile, never on
        re-invocation — it IS the trace counter ``traces`` reports. Each
        firing leaves an attribution record naming the static signature
        and whether it was a fresh bucket or an unexpected retrace."""
        cause = ("new_bucket" if static not in self._traced_statics
                 else "retrace")
        self._traced_statics.add(static)
        self._m_traces.inc(**self._labels)
        obs.record_compile("train.step", cause,
                           trainer=self._labels["trainer"],
                           static=repr(static))

    # -- state ---------------------------------------------------------------

    def init_state(self) -> TrainState:
        root = jax.random.PRNGKey(self.cfg.seed)
        k_init, k_state = jax.random.split(root)
        params = self.task.init(k_init)
        state = TrainState(params, adamw.init(params, self.cfg.opt),
                           jnp.zeros((), jnp.int32), k_state)
        if self.mesh is not None:
            # replicated over the mesh, as the step returns it: a state
            # left on the default device would retrace the step once
            from jax.sharding import NamedSharding, PartitionSpec
            state = jax.device_put(state,
                                   NamedSharding(self.mesh, PartitionSpec()))
        return state

    @property
    def traces(self) -> int:
        """Train-step traces so far — the compile counter. After warmup
        this equals ``len(self.buckets)``: one trace per shape bucket."""
        return int(self._m_traces.value(**self._labels))

    @property
    def buckets(self) -> tuple:
        return tuple(self._execs)

    # -- step construction ---------------------------------------------------

    def _build_step(self, static) -> Callable:
        builder = getattr(self.task, "build_step", None)
        if builder is not None:
            custom = builder(self.cfg, self.mesh, static)
            if custom is not None:
                return custom

        task, cfg, mesh = self.task, self.cfg, self.mesh
        lr_scale_fn = schedule.get(cfg.lr_schedule)

        def step(state: TrainState, arrays):
            self._note_trace(static)
            rng = jax.random.fold_in(state.rng, state.step)

            def loss(p):
                return task.loss(p, arrays, static, rng, mesh=mesh)

            with kops.launch_manifest() as manifest:
                (l, metrics), grads = jax.value_and_grad(
                    loss, has_aux=True)(state.params)
            self._grids[static] = manifest
            lr_scale = lr_scale_fn(state.step, cfg.warmup_steps, cfg.steps)
            new_p, new_o, om = adamw.update(grads, state.opt_state,
                                            state.params, cfg.opt, lr_scale)
            return (TrainState(new_p, new_o, state.step + 1, state.rng),
                    dict(metrics, loss=l, **om))

        return jax.jit(step)

    def _count_grid(self, static, arrays) -> None:
        """Add the launch manifest of ``static``'s trace once for this
        executed step: grid steps walked, and those the step's plan owns
        (Σ chunk_count, from the task's memo; None leaves both out)."""
        grid = self._grids.get(static)
        owned_of = getattr(self.task, "chunks_owned", None)
        if not grid or owned_of is None:
            return
        owned = owned_of(arrays)
        if owned is None:
            return
        for op, (launches, walked) in grid.items():
            self._m_grid.inc(walked, op=op, kind="walked")
            self._m_grid.inc(launches * owned, op=op, kind="owned")

    def executable(self, static) -> Callable:
        """The jitted step for a static signature, built on first use."""
        exe = self._execs.get(static)
        if exe is None:
            exe = self._execs[static] = self._build_step(static)
        return exe

    # -- the loop ------------------------------------------------------------

    def fit(self, *, resume: bool = False, state: Optional[TrainState] = None,
            metrics_cb: Optional[Callable] = None) -> FitResult:
        """Run the training loop to ``cfg.steps`` total steps.

        ``resume=True`` restores the latest complete checkpoint in
        ``cfg.ckpt_dir`` (no-op when none exists yet) and continues from
        its step. ``state=`` overrides the initial state (mutually
        exclusive with ``resume``)."""
        cfg = self.cfg
        if resume and state is not None:
            raise ValueError("pass either resume=True or state=, not both")
        if resume and not cfg.ckpt_dir:
            raise ValueError("resume=True needs TrainerConfig.ckpt_dir")
        if state is None:
            state = self.init_state()
        start = 0
        if resume:
            latest = ckpt.latest_step(cfg.ckpt_dir)
            if latest is not None:
                state = ckpt.restore(state, cfg.ckpt_dir, step=latest)
                start = latest

        history: dict = {}            # step -> loss (replay overwrites)

        def step_fn(st, step):
            with _step_annotation(step), \
                    span("train.step", trainer=self._labels["trainer"],
                         step=int(step)) as root:
                with span("train.sample", step=int(step)):
                    batch = self.data.batch(step)
                with span("train.prepare"):
                    arrays, static = self.task.prepare(
                        batch, plan=self.plan, config=self.config,
                        tune=self.tune, mesh=self.mesh)
                root.set(static=repr(static))
                compiled = static in self._traced_statics
                exe = self.executable(static)
                with span("train.execute" if compiled else "train.compile",
                          static=repr(static)):
                    st, metrics = exe(st, arrays)
                self._m_steps.inc(**self._labels)
                if obs.enabled():
                    self._count_grid(static, arrays)
                with span("train.sync"):
                    loss = float(metrics["loss"])
                history[step] = loss
                if cfg.log_every and step % cfg.log_every == 0:
                    print(f"step {step:5d} loss {loss:.4f} "
                          f"traces {self.traces}", flush=True)
                return st, metrics

        loop = ResilientLoop(
            ResilientLoopConfig(
                cfg.ckpt_dir or "", ckpt_every=cfg.ckpt_every, keep=cfg.keep,
                max_restarts=cfg.max_restarts,
                step_timeout_s=cfg.step_timeout_s,
                straggler_factor=cfg.straggler_factor),
            step_fn, state)
        final = loop.run(cfg.steps, start_step=start, metrics_cb=metrics_cb)
        losses = [history[s] for s in sorted(history)]
        return FitResult(state=final, losses=losses, start_step=start,
                         traces=self.traces, buckets=self.buckets,
                         events=tuple(loop.events))


def _step_annotation(step):
    """A profiler step marker (TensorBoard's step view finds the steps by
    it) while observability is on."""
    if not obs.enabled():
        return contextlib.nullcontext()
    return jax.profiler.StepTraceAnnotation("train", step_num=int(step))


def fit(task, data, trainer: Optional[TrainerConfig] = None, *,
        plan=None, config=None, tune=None, mesh=None, resume: bool = False,
        state: Optional[TrainState] = None,
        metrics_cb: Optional[Callable] = None) -> FitResult:
    """One-call training: ``repro.train.fit(task, data, trainer_cfg)``.

    The functional face of :class:`Trainer` — builds the trainer and runs
    :meth:`Trainer.fit`. ``(plan=, config=, tune=)`` carry the library's
    uniform precedence (plan > config > tune > heuristics) into every
    per-batch planning decision; ``mesh=`` runs graph aggregations sharded
    over :mod:`repro.core.dist_mp`."""
    t = Trainer(task, data, trainer, mesh=mesh, plan=plan, config=config,
                tune=tune)
    return t.fit(resume=resume, state=state, metrics_cb=metrics_cb)
