"""Common building blocks: norms, RoPE, GeoT-backed embedding, attention
(blocked online-softmax for long sequences + KV-cache decode), MLP.

Everything is a pure function over a params pytree of :class:`~repro.models.params.P`
leaves; layer stacks are scanned (see transformer.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.params import P, dense_init, embed_init, ones_init, zeros_init


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, dim: Optional[int] = None):
    dim = dim or cfg.d_model
    prm = {"scale": ones_init((dim,), ("embed",), jnp.float32)}
    if cfg.norm == "layernorm":
        prm["bias"] = zeros_init((dim,), ("embed",), jnp.float32)
    return prm


def apply_norm(prm, x, cfg: ModelConfig, eps: float = 1e-5):
    """Statistics in fp32, elementwise math in the input dtype.

    Deliberate: upcasting the whole tensor makes XLA hoist a bf16→f32
    convert of the *stacked* scan residuals out of the backward loop —
    +2× activation memory (§Perf log #3). The fp32 convert below fuses
    into the reductions, so no f32 copy of x is ever materialized."""
    dt = x.dtype
    if cfg.norm == "layernorm":
        # E[x²]−E[x]² form: jnp.var materializes the full (x−µ)² tensor in
        # fp32; two fused reductions leave no full-size f32 intermediate
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
        var = jnp.maximum(ms - jnp.square(mu), 0.0)
        inv = jax.lax.rsqrt(var + eps)
        out = (x - mu.astype(dt)) * inv.astype(dt) \
            * prm["scale"].value.astype(dt) + prm["bias"].value.astype(dt)
    else:
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        inv = jax.lax.rsqrt(ms + eps)
        out = x * inv.astype(dt) * prm["scale"].value.astype(dt)
    return out


def simple_rms(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (out * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (with partial-rotary support, e.g. StableLM's 25%)
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float, partial: float = 1.0):
    """x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    rot = int(d * partial) // 2 * 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out, xp], -1)


# ---------------------------------------------------------------------------
# GeoT-backed embedding: the backward scatter-add is sort + segment_reduce
# (the paper's op applied to every LM's training step — DESIGN.md §4)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _embed_lookup(table, ids):
    return jnp.take(table, ids, axis=0)


def _embed_fwd(table, ids):
    return jnp.take(table, ids, axis=0), (ids, table.shape[0])


def _embed_bwd(res, g):
    from repro.distributed.sharding import ashard, sharding_active
    ids, vocab = res
    flat_ids = ids.reshape(-1)
    flat_g = g.reshape(-1, g.shape[-1])
    if sharding_active():
        # Under SPMD a *global* argsort of the token stream forces GSPMD to
        # replicate the (B·S, D) cotangent on every device (§Perf log #4 —
        # hypothesis refuted: the GeoT sort pays off per-shard, not
        # globally). Plain scatter-add partitions cleanly instead.
        flat_g = ashard(flat_g, "batch", None)
        dtab = jax.ops.segment_sum(flat_g.astype(jnp.float32), flat_ids,
                                   vocab, indices_are_sorted=False)
        return ashard(dtab, "vocab", "embed").astype(g.dtype), None
    order = jnp.argsort(flat_ids)
    # sorted scatter-add == GeoT segment_reduce (paper §II-B); the output
    # cotangent dtype equals the table dtype (take preserves dtype)
    dtab = jax.ops.segment_sum(
        jnp.take(flat_g, order, axis=0).astype(jnp.float32),
        jnp.take(flat_ids, order), vocab, indices_are_sorted=True)
    return dtab.astype(g.dtype), None


_embed_lookup.defvjp(_embed_fwd, _embed_bwd)


def embedding_init(key, cfg: ModelConfig, dtype):
    return {"table": embed_init(key, cfg.padded_vocab, cfg.d_model, dtype)}


def embed(prm, ids):
    return _embed_lookup(prm["table"].value, ids)


def unembed(prm, x, cfg: ModelConfig):
    logits = jnp.einsum("...d,vd->...v", x, prm["table"].value)
    return (logits * cfg.logit_scale).astype(jnp.float32)


# ---------------------------------------------------------------------------
# TP output projection (hand-scheduled collective)
# ---------------------------------------------------------------------------

def tp_out_project(x, w_param):
    """x @ W with the contraction dim sharded over "model".

    Hand-scheduled TP projection: matmul per-shard via shard_map, psum of
    the bf16 output, FSDP all-gather of W's output dim inside.

    §Perf log #6 (hypothesis REFUTED on this artifact): intended to halve
    the TP all-reduce bytes by reducing in bf16 instead of GSPMD's hoisted
    f32, but XLA:CPU re-hoists the convert past the psum (and past an
    optimization_barrier), so the wire stays f32 and the extra reshards
    cost +11%% collectives — call sites reverted to plain matmuls. Kept as
    opt-in infrastructure: on TPU hardware XLA emits native bf16
    all-reduces, where this is the expected 2× wire win."""
    from repro.distributed.sharding import (current_context, effective_axes,
                                            spec_for_axes)
    w = w_param.value
    ctx = current_context()
    if ctx is None:
        return x @ w
    mesh, plan = ctx
    from jax import shard_map
    from jax.sharding import PartitionSpec as PS
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m_ax = plan.model_axes[0]
    w_spec = spec_for_axes(effective_axes(w_param), w.shape, plan, mesh)
    if w_spec[0] != m_ax or x.shape[-1] % sizes[m_ax] != 0:
        return x @ w                      # contraction not model-sharded
    dspec = tuple(plan.batch_axes) if len(plan.batch_axes) > 1 \
        else plan.batch_axes[0]
    dsize = 1
    for a in plan.batch_axes:
        dsize *= sizes[a]
    if x.shape[0] % dsize != 0:
        dspec = None

    def body(x_l, w_l):
        if w_spec[1] is not None:         # FSDP: regather W's output dim
            w_l = jax.lax.all_gather(w_l, w_spec[1], axis=1, tiled=True)
        return jax.lax.psum(x_l @ w_l, m_ax)      # psum in x.dtype (bf16)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(PS(dspec, *([None] * (x.ndim - 2)), m_ax),
                             PS(*w_spec)),
                   out_specs=PS(dspec, *([None] * (x.ndim - 1))),
                   check_vma=False)
    return fn(x, w)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array          # (B, S_max, KH, D)
    v: jax.Array
    length: jax.Array     # () int32 — tokens already cached


def attention_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    prm = {
        "wq": dense_init(ks[0], d, cfg.q_dim, ("embed", "heads"), dtype),
        "wk": dense_init(ks[1], d, cfg.kv_dim, ("embed", "kv"), dtype),
        "wv": dense_init(ks[2], d, cfg.kv_dim, ("embed", "kv"), dtype),
        "wo": dense_init(ks[3], cfg.q_dim, d, ("heads", "embed"), dtype),
    }
    if cfg.use_bias:
        prm["bq"] = zeros_init((cfg.q_dim,), ("heads",), dtype)
        prm["bk"] = zeros_init((cfg.kv_dim,), ("kv",), dtype)
        prm["bv"] = zeros_init((cfg.kv_dim,), ("kv",), dtype)
        prm["bo"] = zeros_init((d,), ("embed",), dtype)
    if cfg.qk_norm:
        prm["q_norm"] = ones_init((cfg.head_dim,), (None,), jnp.float32)
        prm["k_norm"] = ones_init((cfg.head_dim,), (None,), jnp.float32)
    return prm


def _project_qkv(prm, x, cfg: ModelConfig, positions, apply_rope: bool = True):
    b, s, _ = x.shape
    q = x @ prm["wq"].value
    k = x @ prm["wk"].value
    v = x @ prm["wv"].value
    if cfg.use_bias:
        q, k, v = q + prm["bq"].value, k + prm["bk"].value, v + prm["bv"].value
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = simple_rms(q, prm["q_norm"].value)
        k = simple_rms(k, prm["k_norm"].value)
    if apply_rope and cfg.pos == "rope":
        q = rope(q, positions, cfg.rope_theta, cfg.partial_rotary)
        k = rope(k, positions, cfg.rope_theta, cfg.partial_rotary)
    return q, k, v


def _blocked_attention(q, k, v, causal: bool, block: int = 1024):
    """Online-softmax attention, scanned over KV blocks — O(S·block) memory
    instead of O(S²) (required for the 32k-train/prefill cells to fit HBM).

    The scan body is rematerialized (jax.checkpoint): without it the scan's
    backward saves every block's (B, H, S, block) score tensor — the full
    S×S matrix in fp32 — defeating the blocked formulation (§Perf log #2)."""
    from repro.distributed.sharding import ashard
    b, sq, h, d = q.shape
    skv = k.shape[1]
    g = h // k.shape[2]                             # GQA group size
    scale = 1.0 / jnp.sqrt(d)
    qf = (q * scale).astype(jnp.float32)
    qf = ashard(qf, "batch", None, "act_heads", None)
    nblk = -(-skv // block)
    pad = nblk * block - skv
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = kp.reshape(b, nblk, block, -1, d).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(b, nblk, block, -1, d).transpose(1, 0, 2, 3, 4)
    kb = ashard(kb, None, "batch", None, None, None)
    vb = ashard(vb, None, "batch", None, None, None)
    q_pos = jnp.arange(sq)

    @jax.checkpoint
    def body(carry, inp):
        acc, m, l = carry
        kcb, vcb, blk = inp
        kcb = jnp.repeat(kcb, g, axis=2)
        vcb = jnp.repeat(vcb, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kcb.astype(jnp.float32))
        kv_pos = blk * block + jnp.arange(block)
        mask = kv_pos[None, :] < skv                   # padding mask
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vcb.astype(jnp.float32))
        return (acc, m_new, l), None

    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)    # (B, S, H, D)


def attention(prm, x, cfg: ModelConfig, positions=None, causal: bool = True,
              kv: Optional[tuple] = None, block: int = 1024):
    """Full-sequence attention (training / prefill). kv overrides K/V source
    (cross-attention)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(prm, x, cfg, positions)
    if kv is not None:
        k, v = kv
        causal = False
    out = _blocked_attention(q, k, v, causal, block=block)
    out = out.reshape(b, s, cfg.q_dim) @ prm["wo"].value
    if cfg.use_bias:
        out = out + prm["bo"].value
    return out


def attention_decode(prm, x, cfg: ModelConfig, cache: KVCache,
                     lengths=None):
    """Single-token decode against a KV cache (B, 1, D) → (B, 1, D).

    lengths: optional (B,) int32 per-slot cache lengths — the ragged path
    used by continuous batching (each slot at its own position, with its own
    validity mask); default uses the shared scalar cache.length."""
    b = x.shape[0]
    if lengths is None:
        pos = jnp.broadcast_to(cache.length[None, None], (b, 1))
    else:
        pos = lengths[:, None]
    q, k_new, v_new = _project_qkv(prm, x, cfg, pos)
    if lengths is None:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k_new.astype(cache.k.dtype), cache.length, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v_new.astype(cache.v.dtype), cache.length, axis=1)
        valid = (jnp.arange(k_cache.shape[1]) <= cache.length)[None]
    else:
        rows = jnp.arange(b)
        k_cache = cache.k.at[rows, lengths].set(
            k_new[:, 0].astype(cache.k.dtype))
        v_cache = cache.v.at[rows, lengths].set(
            v_new[:, 0].astype(cache.v.dtype))
        valid = jnp.arange(k_cache.shape[1])[None, :] <= lengths[:, None]
    g = cfg.num_heads // cfg.num_kv_heads
    scale = 1.0 / jnp.sqrt(cfg.head_dim)
    qh = q.reshape(b, 1, cfg.num_kv_heads, g, cfg.head_dim).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qh * scale,
                   k_cache.astype(jnp.float32))
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.q_dim).astype(x.dtype) @ prm["wo"].value
    if cfg.use_bias:
        out = out + prm["bo"].value
    return out, KVCache(k_cache, v_cache, cache.length + 1)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                  num_layers: Optional[int] = None):
    n = num_layers if num_layers is not None else cfg.num_layers
    shape = (n, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def mlp_init(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    prm = {
        "w_up": dense_init(ks[0], cfg.d_model, d_ff, ("embed", "mlp"), dtype),
        "w_down": dense_init(ks[1], d_ff, cfg.d_model, ("mlp", "embed"), dtype),
    }
    if cfg.mlp_gated:
        prm["w_gate"] = dense_init(ks[2], cfg.d_model, d_ff,
                                   ("embed", "mlp"), dtype)
    if cfg.use_bias:
        prm["b_up"] = zeros_init((d_ff,), ("mlp",), dtype)
        prm["b_down"] = zeros_init((cfg.d_model,), ("embed",), dtype)
    return prm


def mlp(prm, x, cfg: ModelConfig):
    act = _ACTS[cfg.act]
    h = x @ prm["w_up"].value
    if cfg.use_bias:
        h = h + prm["b_up"].value
    if cfg.mlp_gated:
        h = act(x @ prm["w_gate"].value) * h
    else:
        h = act(h)
    out = h @ prm["w_down"].value
    if cfg.use_bias:
        out = out + prm["b_down"].value
    return out
