"""Hand-scheduled collectives (shard_map layer).

Explicit counterparts of what GSPMD inserts automatically — used where the
automatic schedule is the bottleneck (§Perf) or where we want compression on
the thin cross-pod link:

  ring_allreduce     — chunked ring reduce-scatter + all-gather via
                       ppermute. One chunk in flight per hop ⇒ each hop's
                       DMA overlaps the next chunk's add (the classic
                       latency-hiding schedule; XLA emits async permutes).
  ring_psum_matmul   — local partial matmul + ring_allreduce of the result.
  hierarchical_psum  — reduce-scatter on the fat intra-pod ICI axis, psum on
                       the thin cross-pod axis, all-gather back.
  compressed_psum    — hierarchical_psum with int8 error-feedback compression
                       on the pod hop (8× fewer DCI bytes).

All functions assume they run inside shard_map with the named axes present;
``make_ring_matmul`` builds the wrapped version.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS
from jax import shard_map

from repro.optim import compression


def _axis_size(axis_name: str) -> int:
    """Static size of a named axis. ``jax.lax.axis_size`` does not exist in
    the pinned JAX; ``psum`` of a literal 1 is evaluated at trace time from
    the axis env, yielding a concrete int usable in Python control flow."""
    return int(jax.lax.psum(1, axis_name))


def _shift_up(x, axis_name: str):
    n = _axis_size(axis_name)
    return jax.lax.ppermute(x, axis_name,
                            perm=[(j, (j + 1) % n) for j in range(n)])


def ring_allreduce(y, axis_name: str):
    """Chunked ring all-reduce of `y` (equivalent to psum(y, axis_name)).

    Falls back to psum when the leading dim doesn't split evenly."""
    n = _axis_size(axis_name)
    if n == 1:
        return y
    m = y.shape[0]
    if m % n != 0:
        return jax.lax.psum(y, axis_name)
    rank = jax.lax.axis_index(axis_name)
    bufs = y.reshape(n, m // n, *y.shape[1:])

    def rs_hop(bufs, step):
        send_idx = jnp.mod(rank - step, n)
        sent = jnp.take(bufs, send_idx, axis=0)
        recv = _shift_up(sent, axis_name)
        recv_idx = jnp.mod(rank - step - 1, n)
        upd = recv + jnp.take(bufs, recv_idx, axis=0)
        return jax.lax.dynamic_update_index_in_dim(bufs, upd, recv_idx, 0), None

    bufs, _ = jax.lax.scan(rs_hop, bufs, jnp.arange(n - 1))
    # device r now holds the fully-reduced chunk (r + 1) mod n

    def ag_hop(bufs, step):
        send_idx = jnp.mod(rank + 1 - step, n)
        sent = jnp.take(bufs, send_idx, axis=0)
        recv = _shift_up(sent, axis_name)
        recv_idx = jnp.mod(rank - step, n)
        return jax.lax.dynamic_update_index_in_dim(bufs, recv, recv_idx, 0), None

    bufs, _ = jax.lax.scan(ag_hop, bufs, jnp.arange(n - 1))
    return bufs.reshape(y.shape)


def ring_psum_matmul(x_local, w_local, axis_name: str):
    """psum_p(x_p @ w_p) with the reduction ring-scheduled.

    x_local: (m, k_local); w_local: (k_local, n)."""
    return ring_allreduce(x_local @ w_local, axis_name)


def hierarchical_psum(x, pod_axis: str, data_axis: str):
    """reduce-scatter intra-pod → cross-pod psum → all-gather intra-pod.

    Equivalent to psum over (pod, data) but the cross-pod (DCI) hop moves
    1/|data| of the bytes."""
    n = _axis_size(data_axis)
    if x.shape[0] % n == 0:
        scat = jax.lax.psum_scatter(x, data_axis, scatter_dimension=0,
                                    tiled=True)
        scat = jax.lax.psum(scat, pod_axis)
        return jax.lax.all_gather(scat, data_axis, axis=0, tiled=True)
    return jax.lax.psum(jax.lax.psum(x, data_axis), pod_axis)


def compressed_psum(x, ef, pod_axis: str, data_axis: str):
    """hierarchical_psum with int8 EF-compression on the cross-pod hop.
    Returns (reduced, new_error_feedback)."""
    n = _axis_size(data_axis)
    if x.shape[0] % n != 0:
        return jax.lax.psum(jax.lax.psum(x, data_axis), pod_axis), ef
    scat = jax.lax.psum_scatter(x, data_axis, scatter_dimension=0, tiled=True)
    v = scat.astype(jnp.float32) + ef
    # shared scale across pods (one scalar pmax) so int8 payloads sum exactly
    absmax = jax.lax.pmax(jnp.max(jnp.abs(v)), pod_axis)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
    new_ef = v - q.astype(jnp.float32) * scale
    qsum = jax.lax.psum(q.astype(jnp.int32), pod_axis)
    scat = qsum.astype(jnp.float32) * scale
    return jax.lax.all_gather(scat, data_axis, axis=0, tiled=True), new_ef


def make_ring_matmul(mesh: Mesh, axis: str = "model"):
    """shard_map-wrapped ring matmul: x (m, K) k-sharded, w (K, n) k-sharded,
    result replicated over `axis`."""
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(PS(None, axis), PS(axis, None)),
        out_specs=PS(None, None),
        check_vma=False)   # replication via ppermute isn't statically inferable
    def fn(x_local, w_local):
        return ring_psum_matmul(x_local, w_local, axis)
    return fn
