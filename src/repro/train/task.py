"""The ``Task`` leg of the orchestration protocol: what the model is and
what its loss means, decoupled from where batches come from (providers)
and how the loop runs (the trainer).

A task implements three methods (the :class:`Task` protocol):

  * ``init(rng) -> params``
  * ``prepare(batch, *, plan=None, config=None, tune=None, mesh=None)
    -> (arrays, static)`` — split a provider batch into the *traced*
    pytree (``arrays``: features, indices, the plan) and a hashable
    *static signature* (``static``: the shape bucket). The trainer keys
    its jitted-executable cache on ``static`` and feeds ``arrays``
    through it — so ``prepare`` is where the compile discipline is won
    or lost.
  * ``loss(params, arrays, static, rng, *, mesh=None) -> (loss, metrics)``
    — pure, differentiable; runs inside the jitted step.

An optional ``build_step(trainer_cfg, mesh, static)`` hook lets a task
supply its own complete ``(state, arrays) -> (state, metrics)`` step
(returning None defers to the trainer's generic one) — how the LM task
revives the pjit build-step pattern of :mod:`repro.distributed.step`
when a parallelism mesh is given.

Plan canonicalization (:class:`NodeClassification`): a
:class:`~repro.core.plan.SegmentPlan`'s *static aux* (kernel config,
tight ``max_chunks``, degree stats) is per-graph — two same-shape graphs
each bringing their own plan would retrace the step, exactly the problem
:mod:`repro.serve.plan_cache` solves for serving. Training borrows the
same move at graph granularity: the first graph of a bucket fixes the
bucket's canonical config + stats, ``max_chunks`` is pinned to the
bucket-static worst case, and every later same-bucket plan swaps only
its chunk-metadata *leaves* under that aux — same treedef, zero
retraces (``Trainer.traces`` asserts it).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.data.graphs import TypedGraph
from repro.models import gnn
from repro.train.trainer import TrainState

__all__ = ["Task", "GraphStatic", "NodeClassification", "LMStatic", "LMTask"]


@runtime_checkable
class Task(Protocol):
    """Structural protocol — any object with these three methods trains."""

    def init(self, rng) -> Any:                        # pragma: no cover
        ...

    def prepare(self, batch, *, plan=None, config=None, tune=None,
                mesh=None) -> tuple:                   # pragma: no cover
        ...

    def loss(self, params, arrays, static, rng, *,
             mesh=None) -> tuple:                      # pragma: no cover
        ...


class GraphStatic(NamedTuple):
    """Hashable shape bucket of a graph batch — the executable-cache key.
    ``shards`` is 0 single-device, else the mesh size. ``sampled`` marks
    mini-batches from the out-of-core pipeline: their arrays carry a
    ``label_mask`` the loss must honor, so they may not share an
    executable with a same-shape full-graph batch (different treedef)."""
    model: str
    num_nodes: int
    num_edges: int
    typed: bool
    shards: int
    sampled: bool = False


@dataclasses.dataclass
class NodeClassification:
    """Full-graph node classification on :mod:`repro.models.gnn` (paper
    §V-F): cross-entropy over per-node logits, accuracy as the metric.

    Works for every model family — homogeneous (``gcn``/``gin``/``sage``/
    ``gat``) on :class:`~repro.data.graphs.Graph` batches and relational
    (``rgcn``/``rgat``) on :class:`~repro.data.graphs.TypedGraph` ones
    (which additionally ride their permutation triple and a canonicalized
    :class:`~repro.core.plan.RelationPlan`).

    ``mesh=`` (via the trainer) partitions each graph over the mesh once
    (memoized) and trains through :mod:`repro.core.dist_mp` — typed
    families stay single-shard, like the layers themselves. Note the
    one-trace-per-bucket guarantee is single-device: a partition's node
    ranges are degree-balanced per graph and ride the pytree treedef, so
    sharded training compiles once per (bucket, partition layout).
    """
    model: str = "gcn"
    d_in: int = 32
    hidden: int = 64
    num_classes: int = 16
    num_layers: int = 3
    heads: int = 1
    num_relations: int = 4
    impl: str = "pallas"

    def __post_init__(self):
        self._dev: dict = {}       # id(g) -> (g, device arrays)
        self._parts: dict = {}     # (id(g), shards) -> (g, part)
        self._pplans: dict = {}    # (id(g), shards, feat, key) -> pplan
        self._buckets: dict = {}   # (static, config, tune) -> canonical aux
        # id(plan.chunk_count) -> (plan, Σ chunk_count): the grid steps a
        # bucket plan owns, a host integer kept beside the memoized plan
        self._owned: dict = {}

    @classmethod
    def from_provider(cls, provider, model: str = "gcn", **kw):
        """Size the task off a provider's metadata (feat / classes /
        relations) — the common wiring of examples and tests."""
        kw.setdefault("num_relations", max(provider.num_relations, 1))
        return cls(model=model, d_in=provider.feat,
                   num_classes=provider.num_classes, **kw)

    @property
    def plan_feat(self) -> int:
        """Representative feature width for config selection: the widest
        layer width, as :func:`repro.models.gnn.make_model_plan` uses."""
        return max(self.d_in, self.hidden, self.num_classes)

    # -- protocol ------------------------------------------------------------

    def init(self, rng):
        return gnn.init(rng, self.model, self.d_in, self.hidden,
                        self.num_classes, self.num_layers, heads=self.heads,
                        num_relations=self.num_relations)

    def prepare(self, batch, *, plan=None, config=None, tune=None, mesh=None):
        from repro.data.pipeline import SampledBatch
        if isinstance(batch, SampledBatch):
            return self._prepare_sampled(batch, plan=plan, mesh=mesh)
        g = batch
        typed = isinstance(g, TypedGraph)
        if typed != (self.model in gnn.TYPED_MODELS):
            raise ValueError(
                f"model {self.model!r} and batch graph type disagree: "
                f"typed={typed} (use a GraphEpochProvider(typed=...) that "
                "matches the model family)")
        shards = int(mesh.devices.size) if mesh is not None else 0
        if typed and shards:
            raise NotImplementedError("typed layers are single-shard for now")
        static = GraphStatic(self.model, g.num_nodes, g.num_edges, typed,
                             shards)
        arrays = dict(self._device_arrays(g, mesh))
        if shards:
            part, pplan = self._partitioned(g, mesh, config, tune)
            arrays["partition"] = part
            arrays["plan"] = plan if plan is not None else pplan
        else:
            arrays["plan"] = (plan if plan is not None
                              else self._bucket_plan(g, static, config, tune))
            if typed:
                arrays["rplan"] = self._bucket_rplan(g, static, config, tune)
        return arrays, static

    def loss(self, params, arrays, static, rng, *, mesh=None):
        logits = gnn.forward(
            params, static.model, arrays["x"], arrays["edge_index"],
            static.num_nodes, arrays.get("deg_inv_sqrt"), self.impl,
            arrays.get("plan"), mesh=mesh,
            partition=arrays.get("partition"),
            edge_type=arrays.get("edge_type"),
            type_perm=arrays.get("type_perm"),
            inv_type_perm=arrays.get("inv_type_perm"),
            type_counts=arrays.get("type_counts"),
            rplan=arrays.get("rplan"))
        labels = arrays["labels"]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        correct = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
        mask = arrays.get("label_mask")
        if mask is None:
            return jnp.mean(logz - gold), {"accuracy": jnp.mean(correct)}
        # sampled mini-batch: only the seed rows carry full (exact or
        # fanout-complete) neighborhoods — supervising padded/neighbor
        # rows would train on truncated aggregations and drop-id noise
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        return (jnp.sum(mask * (logz - gold)) / denom,
                {"accuracy": jnp.sum(mask * correct) / denom})

    def _prepare_sampled(self, batch, *, plan=None, mesh=None):
        """Sampled mini-batches arrive device-ready: the pipeline's
        producer already padded to a bucket, stamped the plan under the
        bucket entry's canonical aux, and issued the host→device copies.
        No memoization here — every batch is a fresh object (the ``_dev``
        id-keyed memo would leak), and none is needed: all the per-shape
        work was paid once, in the shared :class:`~repro.serve.plan_cache.
        PlanCache`."""
        if mesh is not None:
            raise NotImplementedError(
                "sampled mini-batches are single-device for now (shard the "
                "sampler by seed range instead)")
        if self.model in gnn.TYPED_MODELS:
            raise ValueError(
                f"model {self.model!r} is relational; the neighbor sampler "
                "emits homogeneous subgraphs")
        static = GraphStatic(self.model, batch.bucket.num_nodes,
                             batch.bucket.num_edges, False, 0, sampled=True)
        arrays = dict(batch.arrays)
        arrays["plan"] = plan if plan is not None else batch.plan
        return arrays, static

    # -- memoized per-graph state -------------------------------------------

    def _device_arrays(self, g, mesh=None) -> dict:
        """The graph's arrays on the device — replicated over ``mesh``
        when given, so no step copies them from the default device."""
        key = (id(g), None if mesh is None else int(mesh.devices.size))
        hit = self._dev.get(key)
        if hit is not None and hit[0] is g:
            return hit[1]
        arrays = {"x": jnp.asarray(g.x),
                  "edge_index": jnp.asarray(g.edge_index),
                  "labels": jnp.asarray(g.labels),
                  "deg_inv_sqrt": jnp.asarray(g.deg_inv_sqrt)}
        if isinstance(g, TypedGraph):
            arrays.update(edge_type=jnp.asarray(g.edge_type),
                          type_perm=jnp.asarray(g.type_perm),
                          inv_type_perm=jnp.asarray(g.inv_type_perm),
                          type_counts=jnp.asarray(g.type_counts))
        if mesh is not None:
            arrays = jax.device_put(arrays, NamedSharding(mesh, P()))
        # pin g in the memo: id() is only unique among live objects
        self._dev[key] = (g, arrays)
        return arrays

    def _bucket_plan(self, g, static: GraphStatic, config, tune):
        """This graph's plan leaves under the bucket's canonical aux (see
        the module docstring) — same treedef for every graph in the
        bucket, so the step executable never retraces."""
        bkey = ("seg", static, config, tune)
        canon = self._buckets.get(bkey)
        if canon is None:
            p0 = g.make_plan(self.plan_feat, config=config, tune=tune)
            canon = self._buckets[bkey] = (p0.config, p0.stats)
        cfg, stats = canon
        p = g.make_plan(self.plan_feat, config=cfg)       # memoized on g
        key = id(p.chunk_count)
        if key not in self._owned:
            self._owned[key] = (p, int(np.asarray(p.chunk_count).sum()))
        return dataclasses.replace(p.pin_worst_case(), stats=stats)

    def chunks_owned(self, arrays) -> Optional[int]:
        """Σ chunk_count of the bucket plan in ``arrays`` (the (output
        block, chunk) grid steps each of its launches does work in), or
        None for a plan this task did not build. Every segment launch of
        the step runs on that one plan, the backward's too."""
        plan = arrays.get("plan")
        hit = self._owned.get(id(getattr(plan, "chunk_count", None)))
        return hit[1] if hit is not None else None

    def _bucket_rplan(self, g, static: GraphStatic, config, tune):
        bkey = ("rel", static, config, tune)
        canon = self._buckets.get(bkey)
        if canon is None:
            r0 = g.make_relation_plan(self.plan_feat, config=config,
                                      tune=tune)
            canon = self._buckets[bkey] = (r0.config, r0.stats)
        cfg, stats = canon
        r = g.make_relation_plan(self.plan_feat, config=cfg)
        return dataclasses.replace(r, max_groups=r.worst_case_groups,
                                   stats=stats)

    def _partitioned(self, g, mesh, config, tune):
        """The graph's partition and plan, placed on the devices of
        ``mesh`` as the sharded step reads them."""
        shards = int(mesh.devices.size)
        on_shards = NamedSharding(mesh, P(mesh.axis_names[0]))
        pkey = (id(g), shards)
        hit = self._parts.get(pkey)
        if hit is not None and hit[0] is g:
            part = hit[1]
        else:
            part = g.partition(shards).on_mesh(mesh)
            self._parts[pkey] = (g, part)
        plkey = (id(g), shards, self.plan_feat, config, tune)
        pplan = self._pplans.get(plkey)
        if pplan is None:
            pplan = jax.device_put(
                part.make_plan(feat=self.plan_feat, config=config,
                               tune=tune), on_shards)
            self._pplans[plkey] = pplan
        return part, pplan


# ---------------------------------------------------------------------------
# the LM task — the seed's launch/train.py wiring behind the same protocol
# ---------------------------------------------------------------------------

class LMStatic(NamedTuple):
    batch: int
    seq: int


@dataclasses.dataclass
class LMTask:
    """Next-token LM training (:func:`repro.models.lm.loss_fn`) as a Task.

    Single-device it trains through the trainer's generic jitted step.
    With ``mesh=`` its :meth:`build_step` revives
    :func:`repro.distributed.step.build_train_step` — the pjit path with
    param/optimizer/batch shardings from the mesh's
    :class:`~repro.distributed.sharding.ParallelPlan` — behind the same
    ``(state, arrays) -> (state, metrics)`` surface, so
    ``repro.train.fit`` is the one entry point either way. (The pjit
    step keeps its own warmup-cosine schedule; ``TrainerConfig.
    lr_schedule`` applies to the generic step only.)

    The ``(plan=, config=, tune=)`` trio is accepted for protocol
    uniformity but has no effect: token batches carry no segment plans.
    """
    cfg: Any                         # repro.models.config.ModelConfig
    remat_policy: str = "none"
    moe_impl: str = "capacity"
    aux_weight: float = 0.01

    def init(self, rng):
        from repro.models import lm
        return lm.init(rng, self.cfg)

    def prepare(self, batch, *, plan=None, config=None, tune=None, mesh=None):
        arrays = {k: jnp.asarray(v) for k, v in batch.items()}
        b, s = arrays["tokens"].shape
        return arrays, LMStatic(int(b), int(s))

    def loss(self, params, arrays, static, rng, *, mesh=None):
        from repro.models import lm
        return lm.loss_fn(params, self.cfg, arrays,
                          remat_policy=self.remat_policy,
                          moe_impl=self.moe_impl, aux_weight=self.aux_weight)

    def build_step(self, trainer_cfg, mesh, static: LMStatic):
        if mesh is None:
            return None
        from repro.distributed import sharding as shd
        from repro.distributed import step as steplib
        plan = shd.ParallelPlan.for_mesh(mesh)
        ts = steplib.TrainStepConfig(
            opt=trainer_cfg.opt, warmup_steps=trainer_cfg.warmup_steps,
            total_steps=trainer_cfg.steps, remat_policy=self.remat_policy,
            moe_impl=self.moe_impl)
        fn, shardings_for = steplib.build_train_step(self.cfg, mesh, plan, ts)
        box: dict = {}

        def step(state: TrainState, arrays):
            if not box:
                # shardings need concrete params/opt trees — resolved
                # lazily on first call, then reused (outputs already land
                # sharded, so later device_puts are no-ops)
                shapes = {"tokens": (static.batch, static.seq),
                          "labels": (static.batch, static.seq)}
                in_sh, _ = shardings_for(state.params, state.opt_state,
                                         shapes)
                box["in_sh"] = in_sh
                box["jit"] = jax.jit(fn, in_shardings=in_sh)
            in_sh = box["in_sh"]
            params = jax.device_put(state.params, in_sh[0])
            opt = jax.device_put(state.opt_state, in_sh[1])
            batch = {k: jax.device_put(v, in_sh[2][k])
                     for k, v in arrays.items()}
            new_p, new_o, metrics = box["jit"](params, opt, batch, state.step)
            return (TrainState(new_p, new_o, state.step + 1, state.rng),
                    metrics)

        return step
