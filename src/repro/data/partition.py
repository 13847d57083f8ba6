"""Partitioned graphs for sharded message passing (the GraphTensor-style
partition-aware path; see ``docs/distributed_mp.md``).

:func:`partition_graph` splits a :class:`~repro.data.graphs.Graph` into
``num_shards`` pieces for a 1-D device mesh:

  * **nodes** — one contiguous range per shard (``node_ptr``), with the
    boundaries placed by *out-degree* balance so each shard owns roughly
    ``|E| / num_shards`` edges even on power-law graphs;
  * **edges** — every edge lives on the shard that owns its **source**
    node, so the gather side of message passing reads only shard-local
    features (no feature all-gather). Each shard's edge list keeps the
    global dst-sorted order (a subsequence of a sorted list is sorted), is
    padded to the common length ``edges_per_shard``, and carries
    *remapped* indices: ``src_local`` relative to the shard's node block,
    ``dst_global`` in the global segment space. Padding slots use the
    kernels' own drop convention — ``dst = num_nodes`` rows fall outside
    every output window;
  * **halo** — a *cut* edge is one whose destination is owned by another
    shard: its contribution is a partial aggregate that the merge step of
    :mod:`repro.core.dist_mp` combines across shards (psum / pmax /
    softmax stat-merge). :class:`HaloInfo` records how many such edges and
    distinct remote destinations each shard produces.

The result is a registered pytree (device-array leaves, static aux), so a
:class:`PartitionedGraph` threads through ``jax.jit`` closures and
``shard_map`` without retriggering compilation. Round-trips are exact:
``unpartition_nodes(pg, pg.shard_nodes(x)) == x`` and likewise for edges.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.graphs import Graph

__all__ = ["HaloInfo", "PartitionedGraph", "partition_graph",
           "unpartition_nodes", "unpartition_edges"]


@dataclasses.dataclass(frozen=True)
class HaloInfo:
    """Cut-edge metadata of a partition (static, per shard)."""
    cut_edges: Tuple[int, ...]       # edges whose dst is owned elsewhere
    halo_nodes: Tuple[int, ...]      # distinct remote destinations per shard
    total_cut: int
    total_edges: int

    @property
    def cut_fraction(self) -> float:
        return self.total_cut / self.total_edges if self.total_edges else 0.0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PartitionedGraph:
    """A graph split into ``num_shards`` stacked per-shard pieces.

    Leaves are stacked ``(num_shards, ...)`` device arrays that ride
    ``shard_map`` with ``PartitionSpec("shard")``; everything else is
    static aux data.
    """
    # -- leaves (stacked per shard) -----------------------------------------
    src_local: jax.Array    # (S, E_pad) int32: src - node_ptr[s]; pad -> 0
    dst_global: jax.Array   # (S, E_pad) int32: global dst, sorted; pad -> V
    edge_valid: jax.Array   # (S, E_pad) bool: False on padding slots
    edge_gather: jax.Array  # (S, E_pad) int32: global edge slot; pad -> 0
    node_gather: jax.Array  # (S, V_pad) int32: global node row; pad -> 0
    node_valid: jax.Array   # (S, V_pad) bool
    deg: jax.Array          # (V,) float32 global in-degree — the mean
    #                         merge's psum of per-shard counts, evaluated
    #                         once here (it is static partition metadata)
    # -- static aux ---------------------------------------------------------
    num_shards: int
    num_nodes: int           # V (global)
    num_edges: int           # E (global, unpadded)
    nodes_per_shard: int     # V_pad = max shard node-range size
    edges_per_shard: int     # E_pad = max shard edge count
    node_ptr: Tuple[int, ...]   # (S+1,) contiguous node partition
    halo: HaloInfo

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.src_local, self.dst_global, self.edge_valid,
                    self.edge_gather, self.node_gather, self.node_valid,
                    self.deg)
        aux = (self.num_shards, self.num_nodes, self.num_edges,
               self.nodes_per_shard, self.edges_per_shard, self.node_ptr,
               self.halo)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def on_mesh(self, mesh) -> "PartitionedGraph":
        """This partition placed as the ``shard_map`` specs of
        :mod:`repro.core.dist_mp` read it: stacked leaves one shard per
        device of the 1-D ``mesh``, ``deg`` replicated."""
        from jax.sharding import NamedSharding, PartitionSpec
        stacked = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
        leaves, aux = self.tree_flatten()
        placed = [jax.device_put(a, stacked) for a in leaves[:-1]]
        deg = jax.device_put(leaves[-1], NamedSharding(mesh, PartitionSpec()))
        return self.tree_unflatten(aux, placed + [deg])

    # -- shard/unshard helpers ----------------------------------------------
    def shard_nodes(self, x):
        """(V, ...) global node values -> (S, V_pad, ...) stacked local
        blocks (padding rows repeat row 0; they are never read by a valid
        ``src_local``)."""
        return jnp.take(jnp.asarray(x), self.node_gather.reshape(-1),
                        axis=0).reshape(self.num_shards, self.nodes_per_shard,
                                        *np.shape(x)[1:])

    def shard_edges(self, vals):
        """(E, ...) per-edge values (global dst-sorted order) ->
        (S, E_pad, ...) stacked, with padding slots zeroed."""
        vals = jnp.asarray(vals)
        out = jnp.take(vals, self.edge_gather.reshape(-1), axis=0).reshape(
            self.num_shards, self.edges_per_shard, *vals.shape[1:])
        mask = self.edge_valid.reshape(self.num_shards, self.edges_per_shard,
                                       *([1] * (vals.ndim - 1)))
        return jnp.where(mask, out, jnp.zeros((), out.dtype))

    def make_plan(self, feat: Optional[int] = None, config=None,
                  tune: Optional[bool] = None):
        """One :class:`~repro.core.plan.PartitionedPlan` (stacked per-shard
        chunk metadata + a shared config/grid bound) for this partition.

        Host-side, like every plan builder: call it outside ``jit`` (once
        per partition) and pass the result through ``pplan=``/``plan=``."""
        if isinstance(self.dst_global, jax.core.Tracer):
            raise ValueError(
                "PartitionedPlan must be built outside jit (the chunk "
                "metadata is evaluated on the host); build it once with "
                "partition.make_plan(...) and pass it via pplan=/plan=")
        from repro.core.plan import make_partitioned_plan
        return make_partitioned_plan(self, feat=128 if feat is None else feat,
                                     config=config, tune=tune)


def _node_boundaries(outdeg: np.ndarray, num_shards: int) -> np.ndarray:
    """Contiguous node boundaries balanced by out-degree (edge ownership)."""
    v = outdeg.size
    cum = np.concatenate([[0], np.cumsum(outdeg, dtype=np.int64)])
    total = int(cum[-1])
    if total == 0:
        # no edges: plain node-count split
        bounds = np.linspace(0, v, num_shards + 1).round().astype(np.int64)
    else:
        targets = total * np.arange(1, num_shards) / num_shards
        inner = np.searchsorted(cum, targets, side="left")
        bounds = np.concatenate([[0], inner, [v]]).astype(np.int64)
    # monotone + in range even on degenerate degree distributions
    bounds = np.maximum.accumulate(np.clip(bounds, 0, v))
    bounds[0], bounds[-1] = 0, v
    return bounds


def partition_graph(graph: Graph, num_shards: int) -> PartitionedGraph:
    """Contiguous 1-D node partition + source-owned edge shards (see module
    docstring). ``num_shards == 1`` is the identity partition (one shard,
    no padding, no cut edges)."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    v, e = graph.num_nodes, graph.num_edges
    if num_shards > max(v, 1):
        raise ValueError(
            f"num_shards={num_shards} exceeds num_nodes={v}")
    src = np.asarray(graph.edge_index[0], np.int64)
    dst = np.asarray(graph.edge_index[1], np.int64)
    # the per-shard kernels and stat merges assume dst-sorted edge lists
    # (subsequences of a sorted list); fail loudly like make_plan does
    # instead of silently mis-aggregating
    if e and np.any(dst[1:] < dst[:-1]):
        raise ValueError("edge_index[1] (destinations) must be sorted "
                         "non-decreasing to partition the graph")

    outdeg = np.bincount(src, minlength=v) if e else np.zeros(v, np.int64)
    node_ptr = _node_boundaries(outdeg, num_shards)

    # shard of each edge = owner of its source node
    shard_of = (np.searchsorted(node_ptr, src, side="right") - 1 if e
                else np.zeros(0, np.int64))
    counts = np.bincount(shard_of, minlength=num_shards).astype(np.int64)
    e_pad = int(counts.max()) if e else 0
    v_pad = int(np.diff(node_ptr).max()) if v else 0

    src_local = np.zeros((num_shards, e_pad), np.int32)
    dst_global = np.full((num_shards, e_pad), v, np.int32)
    edge_valid = np.zeros((num_shards, e_pad), bool)
    edge_gather = np.zeros((num_shards, e_pad), np.int32)
    node_gather = np.zeros((num_shards, v_pad), np.int32)
    node_valid = np.zeros((num_shards, v_pad), bool)
    cut_edges, halo_nodes = [], []
    for s in range(num_shards):
        lo, hi = int(node_ptr[s]), int(node_ptr[s + 1])
        vs = hi - lo
        node_gather[s, :vs] = np.arange(lo, hi)
        node_valid[s, :vs] = True
        # original order is preserved, so each shard's dst stays sorted
        rows = np.flatnonzero(shard_of == s)
        n = rows.size
        src_local[s, :n] = (src[rows] - lo).astype(np.int32)
        dst_global[s, :n] = dst[rows].astype(np.int32)
        edge_valid[s, :n] = True
        edge_gather[s, :n] = rows.astype(np.int32)
        remote = (dst[rows] < lo) | (dst[rows] >= hi)
        cut_edges.append(int(remote.sum()))
        halo_nodes.append(int(np.unique(dst[rows][remote]).size))

    halo = HaloInfo(cut_edges=tuple(cut_edges), halo_nodes=tuple(halo_nodes),
                    total_cut=int(sum(cut_edges)), total_edges=e)
    return PartitionedGraph(
        src_local=jnp.asarray(src_local),
        dst_global=jnp.asarray(dst_global),
        edge_valid=jnp.asarray(edge_valid),
        edge_gather=jnp.asarray(edge_gather),
        node_gather=jnp.asarray(node_gather),
        node_valid=jnp.asarray(node_valid),
        deg=jnp.asarray((np.bincount(dst, minlength=v) if e
                         else np.zeros(v)).astype(np.float32)),
        num_shards=num_shards,
        num_nodes=v,
        num_edges=e,
        nodes_per_shard=v_pad,
        edges_per_shard=e_pad,
        node_ptr=tuple(int(b) for b in node_ptr),
        halo=halo,
    )


def unpartition_nodes(pg: PartitionedGraph, stacked):
    """Inverse of :meth:`PartitionedGraph.shard_nodes`: scatter stacked
    (S, V_pad, ...) local node blocks back to global (V, ...) order."""
    stacked = jnp.asarray(stacked)
    flat = stacked.reshape(pg.num_shards * pg.nodes_per_shard,
                           *stacked.shape[2:])
    out = jnp.zeros((pg.num_nodes, *stacked.shape[2:]), stacked.dtype)
    idx = jnp.where(pg.node_valid, pg.node_gather, pg.num_nodes).reshape(-1)
    # out-of-range scatter slots (padding) are dropped
    return out.at[idx].set(flat, mode="drop")


def unpartition_edges(pg: PartitionedGraph, stacked):
    """Inverse of :meth:`PartitionedGraph.shard_edges`: scatter stacked
    (S, E_pad, ...) per-edge values back to global (E, ...) order."""
    stacked = jnp.asarray(stacked)
    flat = stacked.reshape(pg.num_shards * pg.edges_per_shard,
                           *stacked.shape[2:])
    out = jnp.zeros((pg.num_edges, *stacked.shape[2:]), stacked.dtype)
    idx = jnp.where(pg.edge_valid, pg.edge_gather, pg.num_edges).reshape(-1)
    return out.at[idx].set(flat, mode="drop")
