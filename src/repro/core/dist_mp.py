"""Sharded message passing over a device mesh (data -> plan -> mp -> models).

The single-device :mod:`repro.core.mp` primitive becomes a two-stage
program on a 1-D ``"shard"`` mesh:

  1. **local** — every shard runs the *same* single-launch fused Pallas
     aggregation (:mod:`repro.kernels.gather_segment_reduce` /
     :mod:`repro.kernels.segment_softmax`) over its own edge shard, with a
     per-shard :class:`~repro.core.plan.SegmentPlan` sliced out of a
     stacked :class:`~repro.core.plan.PartitionedPlan`. Features are read
     shard-locally (edges live with their source node — see
     :mod:`repro.data.partition`), so the gather never crosses the mesh.
  2. **merge** — cut-edge (halo) contributions are combined across shards
     with the reduce's own algebra:

       sum      psum (or :func:`repro.distributed.collectives.ring_allreduce`)
       mean     psum of the partial *sums* and of the per-destination
                *counts*, then one divide — never an average of averages
       max      pmax, rendered as ``all_gather`` + max so the merge stays
                differentiable (``lax.pmax`` has no differentiation rule).
                At *tied* maxima spanning shards the gradient is a valid
                subgradient (it sums to the cotangent over each segment)
                but may split ties differently than the single-device
                even split — exact tie parity would require
                re-materializing the (|E|, F) message tensor, the very
                thing the fused kernels avoid; ties are measure-zero for
                continuous features
       softmax  two-stage online-softmax stat merge: each shard's fused
                kernel output is exact w.r.t. its local statistics; the
                global answer is a per-segment rescale by ``z_loc/z_glob``
                with both sum-exps measured at the pmax'd global max

All entry points accept *global* arrays (node features ``(V, F)``,
per-edge values ``(E,)`` in the graph's dst-sorted order) and return the
replicated global result, so a sharded call is a drop-in replacement for
its single-device twin — ``mp_sharded(x, pg, ...) == mp(x, edge_index,
...)`` up to float-summation order.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS
from jax import shard_map

from repro.core import ops as geot
from repro.core.config_space import KernelConfig

__all__ = ["make_shard_mesh", "mp_sharded", "mp_transform_sharded",
           "segment_softmax_sharded"]

_AXIS = "shard"


def make_shard_mesh(num_shards: int, axis_name: str = _AXIS) -> Mesh:
    """A 1-D mesh over the first ``num_shards`` local devices. Host
    platforms fake the device count via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    devs = jax.devices()
    if len(devs) < num_shards:
        raise ValueError(
            f"mesh needs {num_shards} devices, found {len(devs)} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count)")
    import numpy as np
    return Mesh(np.asarray(devs[:num_shards]), (axis_name,))


def _check(pg, mesh: Optional[Mesh], axis_name: str) -> Mesh:
    mesh = make_shard_mesh(pg.num_shards, axis_name) if mesh is None else mesh
    if mesh.shape[axis_name] != pg.num_shards:
        raise ValueError(
            f"mesh axis {axis_name!r} has {mesh.shape[axis_name]} devices "
            f"but the partition has {pg.num_shards} shards")
    return mesh


def _allreduce(y, axis_name: str, collective: str):
    if collective == "ring":
        from repro.distributed import collectives
        return collectives.ring_allreduce(y, axis_name)
    if collective != "psum":
        raise ValueError(f"unknown collective: {collective!r}")
    return jax.lax.psum(y, axis_name)


def _pmax(y, axis_name: str):
    # pmax with a VJP: all-gather the shard partials and reduce with jnp.max
    # (lax.pmax itself has no differentiation rule)
    return jnp.max(jax.lax.all_gather(y, axis_name), axis=0)


def _edge_stack(pg, vals):
    """Per-edge values -> stacked (S, E_pad, ...): accepts global (E, ...)
    order or an already-stacked array (e.g. sharded softmax output)."""
    vals = jnp.asarray(vals)
    if vals.ndim >= 2 and vals.shape[:2] == (pg.num_shards,
                                             pg.edges_per_shard):
        return vals
    if vals.shape[:1] == (pg.num_edges,):
        return pg.shard_edges(vals)
    raise ValueError(
        f"per-edge values must be global ({pg.num_edges}, ...) or stacked "
        f"({pg.num_shards}, {pg.edges_per_shard}, ...), got {vals.shape}")


def mp_sharded(x, pg, *, reduce: str = "sum", edge_weight=None, pplan=None,
               mesh: Optional[Mesh] = None, impl: str = "pallas",
               config: Optional[KernelConfig] = None,
               collective: str = "psum", axis_name: str = _AXIS):
    """Sharded message passing: ``Y[d] = reduce_{(s,d) in E} (w_e *) X[s]``
    over a :class:`~repro.data.partition.PartitionedGraph`.

    ``x``: global (V, F) node features; ``edge_weight``: global (E,) or
    stacked (S, E_pad) per-edge weights; ``pplan``: a
    :class:`~repro.core.plan.PartitionedPlan` (built on demand when
    omitted). Returns the replicated global (V, F) aggregate, matching
    ``core.mp.mp`` (max fills empty neighbourhoods with 0)."""
    if reduce not in ("sum", "mean", "max"):
        raise ValueError(f"unknown reduce: {reduce!r}")
    mesh = _check(pg, mesh, axis_name)
    if pplan is None:
        pplan = pg.make_plan(feat=int(x.shape[-1]), config=config)
    v = pg.num_nodes
    x_stack = pg.shard_nodes(x)
    w_stack = None if edge_weight is None else _edge_stack(pg, edge_weight)
    # mean = psum of the local fused *sums* and of the per-destination
    # counts, then one divide — the halo-correct algebra (never a mean of
    # means). The count psum is static partition metadata, already merged
    # into pg.deg at partition time, so the runtime pays one collective.
    kernel_reduce = "sum" if reduce == "mean" else reduce

    def local(xb, sb, db, cfb, ccb, degb, wb):
        plan = pplan.local_plan(cfb, ccb)
        if wb is None:
            part = geot.index_segment_reduce(xb[0], sb[0], db[0], v,
                                             kernel_reduce, impl, None, plan)
        else:
            part = geot.index_weight_segment_reduce(xb[0], sb[0], wb[0],
                                                    db[0], v, kernel_reduce,
                                                    impl, None, plan)
        if reduce == "max":
            y = _pmax(part, axis_name)
            return jnp.where(y == -jnp.inf, jnp.zeros_like(y), y)
        s = _allreduce(part, axis_name, collective)
        if reduce == "mean":
            s = s / jnp.maximum(degb, 1.0)[:, None].astype(s.dtype)
        return s

    args = [x_stack, pg.src_local, pg.dst_global, pplan.chunk_first,
            pplan.chunk_count]
    in_specs = [PS(axis_name)] * 5 + [PS()]    # deg rides replicated
    args.append(pg.deg)
    if w_stack is None:
        fn = lambda a, b, c, d, e, f: local(a, b, c, d, e, f, None)  # noqa: E731
    else:
        fn, args, in_specs = local, args + [w_stack], in_specs + [PS(axis_name)]
    return shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=PS(), check_vma=False)(*args)


def mp_transform_sharded(x, w, pg, *, reduce: str = "sum", edge_weight=None,
                         pplan=None, mesh: Optional[Mesh] = None,
                         impl: str = "pallas",
                         config: Optional[KernelConfig] = None,
                         collective: str = "psum", order: str = "auto",
                         axis_name: str = _AXIS):
    """Sharded ``mp_transform``: aggregate(X·W) or aggregate(X)·W with the
    same cost-model reordering as the single-device path — the dense
    matmul runs on the replicated side of the mesh, the aggregation runs
    fused per shard. Non-linear reduces (``max``) pin transform-first
    (one shared resolver with ``mp_transform``: :func:`.mp.resolve_order`)."""
    from repro.core.mp import resolve_order
    # allow_fused=False: the one-launch SpMM+GEMM arm is single-device only —
    # the sharded reduce's collective merge (psum of partial aggregates /
    # mean counts) must happen *between* aggregate and transform, so the
    # per-shard (S, d_in) partials have to surface
    order = resolve_order(reduce, order, int(x.shape[-1]),
                          int(w.shape[-1]), plan=pplan,
                          num_edges=pg.num_edges, num_nodes=pg.num_nodes,
                          config=config, allow_fused=False)
    kw = dict(reduce=reduce, edge_weight=edge_weight, pplan=pplan, mesh=mesh,
              impl=impl, config=config, collective=collective,
              axis_name=axis_name)
    if order == "aggregate_first":
        return mp_sharded(x, pg, **kw) @ w
    return mp_sharded(x @ w, pg, **kw)


def segment_softmax_sharded(e, pg, *, pplan=None, mesh: Optional[Mesh] = None,
                            impl: str = "pallas",
                            config: Optional[KernelConfig] = None,
                            axis_name: str = _AXIS):
    """Sharded segment softmax over destinations (GAT attention).

    ``e``: global (E,) or (E, H) logits. Each shard runs the fused
    single-launch softmax kernel over its local edges, then the local
    answers are corrected by the two-stage online-softmax merge:

        m_glob = pmax_s(segment_max(e))          (running max)
        z_loc  = segment_sum(exp(e - m_glob))    (sum-exp at the global max)
        p      = p_loc * z_loc / psum_s(z_loc)

    Segments fully local to one shard rescale by exactly 1. Returns the
    **stacked** (S, E_pad[, H]) attention weights — feed them straight
    back into :func:`mp_sharded` as ``edge_weight``, or map to global
    order with :func:`repro.data.partition.unpartition_edges`."""
    mesh = _check(pg, mesh, axis_name)
    if pplan is None:
        feat = int(e.shape[-1]) if jnp.ndim(e) > 1 else 1
        pplan = pg.make_plan(feat=feat, config=config)
    v = pg.num_nodes
    e_stack = _edge_stack(pg, e)

    def local(eb, db, vb, cfb, ccb):
        el, dl, valid = eb[0], db[0], vb[0]
        plan = pplan.local_plan(cfb, ccb)
        p_loc = geot.segment_softmax(el, dl, v, impl, None, plan)
        # the merge's (m, z) statistics run as jnp segment ops — recorded
        # under "merge:" so the fusion accounting stays honest: they are
        # the collective halo algebra, not a fallback of the aggregation
        # (which is the fused p_loc launch above)
        from repro.kernels import ops as kops
        kops.account("merge", "segment_softmax_stats")
        # local online stats over valid edges only (padding carries
        # dst == V and drops out of the scatter)
        squeeze = el.ndim == 1
        e2 = el[:, None] if squeeze else el
        m_loc = jax.lax.stop_gradient(jax.ops.segment_max(
            e2, dl, v, indices_are_sorted=True))
        m_glob = _pmax(m_loc, axis_name)
        m_safe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
        z_loc = jax.ops.segment_sum(
            jnp.exp(e2 - jnp.take(m_safe, dl, axis=0, mode="fill",
                                  fill_value=0))
            * valid[:, None].astype(e2.dtype),
            dl, v, indices_are_sorted=True)
        z_glob = jax.lax.psum(z_loc, axis_name)
        # z_loc is this shard's sum-exp measured at the *global* max, so
        # p_glob = p_loc * z_loc / z_glob per segment (the exp(m_loc - m_glob)
        # of the textbook merge is already inside z_loc); locally-empty
        # segments have z_loc = 0 and never feed a local edge
        factor = z_loc / jnp.maximum(z_glob, 1e-20)
        p2 = (p_loc[:, None] if squeeze else p_loc)
        p2 = jnp.where(
            valid[:, None],
            p2 * jnp.take(factor, dl, axis=0, mode="fill", fill_value=0),
            0.0)
        return p2[:, 0] if squeeze else p2

    out = shard_map(local, mesh=mesh, in_specs=(PS(axis_name),) * 5,
                    out_specs=PS(axis_name), check_vma=False)(
        e_stack, pg.dst_global, pg.edge_valid, pplan.chunk_first,
        pplan.chunk_count)
    # out_specs concatenate the per-shard blocks; restack to (S, E_pad, ...)
    return out.reshape(pg.num_shards, pg.edges_per_shard, *out.shape[1:])
