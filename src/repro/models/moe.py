"""Mixture-of-Experts layer built on GeoT segment ops (DESIGN.md §4).

Token→expert routing *is* a sorted segment-reduction problem:

  dispatch — assignments sorted by expert id (the sortedness contract of
             paper §II-B), positions-within-expert from the segment offsets;
  experts  — grouped GEMM over expert segments (``segment_matmul``) in the
             dropless path, or a dense (E, C, D) einsum in the capacity path
             (EP-shardable: `expert` axis → mesh "model");
  combine  — ``index_weight_segment_reduce`` keyed by token id (already
             sorted) with the router probabilities as weights — *exactly*
             the paper's fused SpMM op (§IV).

Two implementations:
  * ``capacity`` — static-shape GShard-style buffers; the pjit/dry-run path.
  * ``ragged``   — dropless sort + segment_matmul; single-host path that
                   exercises the Pallas grouped-GEMM kernel.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import ops as geot
from repro.models.config import ModelConfig
from repro.models.params import P, dense_init
from repro.models import layers


def moe_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    std = 1.0 / jnp.sqrt(d)
    prm = {
        "router": dense_init(ks[0], d, e, ("embed", "expert"), jnp.float32),
        "w_up": P(jax.random.normal(ks[1], (e, d, f), dtype) * std,
                  ("expert", "embed", "mlp")),
        "w_gate": P(jax.random.normal(ks[2], (e, d, f), dtype) * std,
                    ("expert", "embed", "mlp")),
        "w_down": P(jax.random.normal(ks[3], (e, f, d), dtype) * (std / 4),
                    ("expert", "mlp", "embed")),
    }
    if cfg.num_shared_experts:
        prm["shared"] = layers.mlp_init(
            ks[4], cfg, dtype, d_ff=cfg.moe_d_ff * cfg.num_shared_experts)
    return prm


def _route(prm, x2d, cfg: ModelConfig):
    """Router: top-k expert ids + combine weights per token."""
    logits = (x2d.astype(jnp.float32) @ prm["router"].value)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch-style)
    e = cfg.num_experts
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e[..., 0], e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return top_e.astype(jnp.int32), top_p.astype(x2d.dtype), aux


def _experts_dense(prm, xd, cfg: ModelConfig):
    """(E, C, D) → (E, C, D), sharded over the expert axis under pjit."""
    act = layers._ACTS[cfg.act]
    hu = jnp.einsum("ecd,edf->ecf", xd, prm["w_up"].value)
    hg = jnp.einsum("ecd,edf->ecf", xd, prm["w_gate"].value)
    return jnp.einsum("ecf,efd->ecd", act(hg) * hu, prm["w_down"].value)


def moe_capacity(prm, x, cfg: ModelConfig, capacity: Optional[int] = None):
    """Static-shape MoE (pjit path). x: (B, S, D) → (B, S, D), aux loss."""
    from repro.distributed.sharding import ashard
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    top_e, top_p, aux = _route(prm, x2d, cfg)
    k = cfg.top_k
    e = cfg.num_experts
    if capacity is None:
        capacity = max(1, int(t * k * cfg.capacity_factor / e))
        capacity = min(capacity, t)
    capacity = -(-capacity // 32) * 32        # shardable over the data axes
    a = t * k

    e_flat = top_e.reshape(a)
    w_flat = top_p.reshape(a)
    tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)   # sorted ✓

    # --- dispatch: sort assignments by expert (GeoT sortedness contract) ---
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = jnp.take(e_flat, order)
    pos_sorted = jnp.arange(a, dtype=jnp.int32) - jnp.take(
        jnp.searchsorted(e_sorted, jnp.arange(e, dtype=jnp.int32),
                         side="left").astype(jnp.int32), e_sorted)
    inv = jnp.zeros((a,), jnp.int32).at[order].set(
        jnp.arange(a, dtype=jnp.int32))
    pos = jnp.take(pos_sorted, inv)                 # position in expert, orig order
    keep = pos < capacity
    slot = jnp.where(keep, e_flat * capacity + pos, e * capacity)

    # the (T·k, D) gathered message tensor is batch-aligned (tok_flat is
    # token-sorted) — pin it to the data axes or GSPMD replicates the gather
    msg = ashard(jnp.take(x2d, tok_flat, axis=0), "batch", None)
    xd = jnp.zeros((e * capacity, d), x.dtype).at[slot].set(msg, mode="drop")
    # EP: experts on "model", capacity slots on the data axes (GShard layout)
    xd3 = ashard(xd.reshape(e, capacity, d), "expert", "capacity", None)
    yd = _experts_dense(prm, xd3, cfg)
    yd = ashard(yd, "expert", "capacity", None).reshape(e * capacity, d)

    # --- combine: the paper's fused op — gather rows by slot, weight by
    # router prob, segment-reduce over (sorted) token ids (§IV) ---
    slot_safe = jnp.minimum(slot, e * capacity - 1)
    out2d = geot.index_weight_segment_reduce(
        yd, slot_safe, jnp.where(keep, w_flat, 0.0), tok_flat, t)

    if cfg.num_shared_experts:
        out2d = out2d + layers.mlp(prm["shared"], x2d, cfg)
    return out2d.reshape(b, s, d).astype(x.dtype), aux


def moe_ragged(prm, x, cfg: ModelConfig, impl: str = "ref"):
    """Dropless MoE via sort + grouped GEMM (single-host / kernel path)."""
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    top_e, top_p, aux = _route(prm, x2d, cfg)
    k = cfg.top_k
    a = t * k
    e_flat = top_e.reshape(a)
    w_flat = top_p.reshape(a)
    tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    order = jnp.argsort(e_flat, stable=True)
    tok_sorted = jnp.take(tok_flat, order)
    group_sizes = jnp.bincount(e_flat, length=cfg.num_experts).astype(jnp.int32)

    xs = jnp.take(x2d, tok_sorted, axis=0)
    act = layers._ACTS[cfg.act]
    hu = geot.segment_matmul(xs, group_sizes, prm["w_up"].value, impl=impl)
    hg = geot.segment_matmul(xs, group_sizes, prm["w_gate"].value, impl=impl)
    ys = geot.segment_matmul(act(hg) * hu, group_sizes, prm["w_down"].value,
                             impl=impl)

    # combine in original (token-sorted) assignment order — fused SpMM (§IV)
    inv = jnp.zeros((a,), jnp.int32).at[order].set(
        jnp.arange(a, dtype=jnp.int32))
    out2d = geot.index_weight_segment_reduce(ys, inv, w_flat, tok_flat, t)

    if cfg.num_shared_experts:
        out2d = out2d + layers.mlp(prm["shared"], x2d, cfg)
    return out2d.reshape(b, s, d).astype(x.dtype), aux


def moe_shard_map(prm, x, cfg: ModelConfig):
    """Expert-parallel MoE via shard_map (§Perf iteration #5).

    GSPMD partitions the global dispatch scatter by materialising a
    (T·k, D) u32 index grid and all-gathering it (~69 GB/chip/layer on the
    qwen3-moe train cell — measured). But the MoE input is already
    *replicated over the model axis* (it feeds TP attention), so dispatch
    can be entirely LOCAL: each device selects the assignments that target
    its own E/|model| experts, builds its capacity buffer with the GeoT
    sort + fused combine (the paper's ops, applied per shard), and the only
    cross-device traffic is the same (T_local, D) psum a dense TP MLP pays.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as PS
    from repro.distributed.sharding import current_context, spec_for_axes

    mesh, plan = current_context()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m_ax = plan.model_axes[0]
    msize = sizes[m_ax]
    d_axes = plan.batch_axes
    dsize = 1
    for a in d_axes:
        dsize *= sizes[a]
    e = cfg.num_experts
    b, s, d = x.shape
    t = b * s
    if e % msize != 0 or (b % dsize != 0 and t % dsize != 0):
        return moe_capacity(prm, x, cfg)         # unshardable → global path
    e_m = e // msize
    t_loc = t // dsize
    k = cfg.top_k
    cap = max(1, int(t_loc * k * cfg.capacity_factor / e))
    cap = -(-cap // 8) * 8

    x2d = x.reshape(t, d)
    top_e, top_p, aux = _route(prm, x2d, cfg)
    dspec = tuple(d_axes) if len(d_axes) > 1 else d_axes[0]

    from repro.distributed.sharding import effective_axes
    wu, wg, wd = prm["w_up"].value, prm["w_gate"].value, prm["w_down"].value
    wu_spec = spec_for_axes(effective_axes(prm["w_up"]), wu.shape, plan, mesh)
    wg_spec = spec_for_axes(effective_axes(prm["w_gate"]), wg.shape, plan, mesh)
    wd_spec = spec_for_axes(effective_axes(prm["w_down"]), wd.shape, plan, mesh)

    def gather_dim(w, spec, dim):
        if spec[dim] is not None:
            names = spec[dim]
            return jax.lax.all_gather(w, names, axis=dim, tiled=True)
        return w

    def body(x_loc, te_loc, tp_loc, wu_l, wg_l, wd_l):
        m_rank = jax.lax.axis_index(m_ax)
        # FSDP: rebuild the full hidden dim of the local experts' weights
        wu_f = gather_dim(wu_l, wu_spec, 1)
        wg_f = gather_dim(wg_l, wg_spec, 1)
        wd_f = gather_dim(wd_l, wd_spec, 2)

        a = t_loc * k
        e_flat = te_loc.reshape(a)
        w_flat = tp_loc.reshape(a)
        tok_flat = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), k)

        # GeoT dispatch (paper §II-B): sort assignments by expert id —
        # local to this shard, no collective
        order = jnp.argsort(e_flat, stable=True)
        e_sorted = jnp.take(e_flat, order)
        pos_sorted = jnp.arange(a, dtype=jnp.int32) - jnp.take(
            jnp.searchsorted(e_sorted, jnp.arange(e, dtype=jnp.int32),
                             side="left").astype(jnp.int32), e_sorted)
        inv = jnp.zeros((a,), jnp.int32).at[order].set(
            jnp.arange(a, dtype=jnp.int32))
        pos = jnp.take(pos_sorted, inv)
        mine = (e_flat // e_m) == m_rank
        keep = jnp.logical_and(pos < cap, mine)
        slot = jnp.where(keep, (e_flat - m_rank * e_m) * cap + pos, e_m * cap)

        xd = jnp.zeros((e_m * cap, d), x.dtype).at[slot].set(
            jnp.take(x_loc, tok_flat, axis=0), mode="drop")
        xd3 = xd.reshape(e_m, cap, d)
        act = layers._ACTS[cfg.act]
        hu = jnp.einsum("ecd,edf->ecf", xd3, wu_f)
        hg = jnp.einsum("ecd,edf->ecf", xd3, wg_f)
        yd = jnp.einsum("ecf,efd->ecd", act(hg) * hu, wd_f)
        yd = yd.reshape(e_m * cap, d)

        # GeoT combine (paper §IV): fused gather+weight+segment-reduce over
        # the (sorted) token ids — local; then one TP-style psum
        slot_safe = jnp.minimum(slot, e_m * cap - 1)
        out_part = geot.index_weight_segment_reduce(
            yd, slot_safe, jnp.where(keep, w_flat, 0.0), tok_flat, t_loc)
        # combine psum rides the wire in bf16 — inside shard_map the wire
        # dtype is ours to pick (§Perf log #7): halves combine bytes
        return jax.lax.psum(out_part.astype(x.dtype), m_ax)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(PS(dspec, None), PS(dspec, None), PS(dspec, None),
                  wu_spec, wg_spec, wd_spec),
        out_specs=PS(dspec, None),
        check_vma=False)
    out2d = fn(x2d, top_e, top_p, wu, wg, wd).astype(x.dtype)

    if cfg.num_shared_experts:
        out2d = out2d + layers.mlp(prm["shared"], x2d, cfg)
    return out2d.reshape(b, s, d), aux


def moe(prm, x, cfg: ModelConfig, impl: str = "capacity"):
    if impl == "capacity":
        from repro.distributed.sharding import sharding_active
        if sharding_active():
            return moe_shard_map(prm, x, cfg)
        return moe_capacity(prm, x, cfg)
    return moe_ragged(prm, x, cfg, impl="ref" if impl == "ragged" else impl)
