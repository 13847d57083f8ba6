"""Plain ``jax.numpy`` reference of the benchmark's models, in float32.

It imports nothing of the program and takes nothing the program made: the
weights come from :func:`init_params` (the benchmark's own maker, from the
seed), the graph from :mod:`.graphs`. The models follow OGB's reference
scripts for ogbn-arxiv as the configuration files state them: GCN with the
symmetric D^-1/2 A D^-1/2 of the in-degree (no self-loops, no batch norm,
no dropout), GraphSAGE with the mean aggregator and a root weight, ReLU
between layers, cross entropy over every node, Adam with torch's update.

``precision`` names how every matmul, forward and backward, is computed:
``"highest"`` is float32 (the reference); ``"high"`` is three bf16 passes
with float32 accumulation (the control that the comparison has to fail).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.chip.compare import leaf_norms

MODELS = ("gcn", "sage")


PRECISIONS = ("highest", "high")


def _dot3(a, b):
    """a @ b from three bf16 products (hi·hi + hi·lo + lo·hi) with float32
    accumulation, as XLA's ``high`` precision computes it. ``hi`` takes the
    top 16 bits of each float32 by masking, so that no pair of converts
    exists that a compiler allowed excess precision could drop."""
    def split(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                          jnp.float32)
        return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)


@jax.custom_vjp
def dot_high(a, b):
    return _dot3(a, b)


def _dot_high_fwd(a, b):
    return _dot3(a, b), (a, b)


def _dot_high_bwd(res, g):
    a, b = res
    return _dot3(g, b.T), _dot3(a.T, g)


dot_high.defvjp(_dot_high_fwd, _dot_high_bwd)


def matmul(a, b, precision: str):
    """``highest``: float32. ``high``: three bf16 passes — the TPU's own
    ``Precision.HIGH``; other backends ignore that flag, so there it is
    computed by :func:`dot_high`."""
    if precision == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        if jax.default_backend() == "tpu":
            return jnp.dot(a, b, precision=jax.lax.Precision.HIGH)
        return dot_high(a, b)
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def layer_dims(cfg: dict) -> tuple:
    return ((cfg["num_features"],) + (cfg["hidden_channels"],) * (cfg["num_layers"] - 1)
            + (cfg["num_classes"],))


def weight_names(model: str) -> tuple:
    return {"gcn": ("w",), "sage": ("w_self", "w_neigh")}[model]


def jax_key(seed: int, stream: int):
    """A PRNG key from any whole number (wider than 32 bits too)."""
    s = int(seed) % 2**64
    key = jax.random.PRNGKey(s & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, s >> 31), stream)


@functools.partial(jax.jit, static_argnames=("model", "dims"))
def _init(key, model: str, dims: tuple):
    params = []
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        d_in, d_out = dims[i], dims[i + 1]
        layer = {}
        for name, kk in zip(weight_names(model),
                            jax.random.split(k, len(weight_names(model)))):
            layer[name] = (jax.random.normal(kk, (d_in, d_out), jnp.float32)
                           / jnp.sqrt(jnp.float32(d_in)))
        layer["b"] = jnp.zeros((d_out,), jnp.float32)
        params.append(layer)
    return params


def init_params(cfg: dict, seed: int) -> list:
    """The model's float32 weights, made on the device in one call."""
    return _init(jax_key(seed, 1), cfg["model"], layer_dims(cfg))


def forward(params, model: str, x, src, dst, num_nodes: int,
            precision: str = "highest"):
    """Logits of every node. Edges with ``dst >= num_nodes`` are padding
    and are dropped."""
    ones = jnp.ones(dst.shape, jnp.float32)
    deg = jax.ops.segment_sum(ones, dst, num_nodes, mode="drop")
    dis = jax.lax.rsqrt(jnp.maximum(deg, 1.0))
    pad = jnp.concatenate([dis, jnp.zeros((1,), jnp.float32)])
    w_e = dis[src] * pad[jnp.minimum(dst, num_nodes)]
    h = x
    for i, p in enumerate(params):
        if model == "gcn":
            agg = jax.ops.segment_sum(h[src] * w_e[:, None], dst, num_nodes,
                                      mode="drop")
            h = matmul(agg, p["w"], precision) + p["b"]
        else:
            agg = jax.ops.segment_sum(h[src], dst, num_nodes, mode="drop")
            agg = agg / jnp.maximum(deg, 1.0)[:, None]
            h = (matmul(h, p["w_self"], precision)
                 + matmul(agg, p["w_neigh"], precision) + p["b"])
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


def loss(params, model: str, x, src, dst, labels, num_nodes: int,
         rows: int, precision: str = "highest"):
    """Mean cross entropy over the first ``rows`` nodes (all of them in the
    reference; fewer only where a fault leaves part of the batch out)."""
    logits = forward(params, model, x, src, dst, num_nodes,
                     precision)[:rows]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:rows, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


@functools.partial(jax.jit, static_argnames=("model", "num_nodes", "rows",
                                             "opt", "precision"))
def _adam_step(params, m, v, t, x, src, dst, labels, *, model, num_nodes,
               rows, opt, precision):
    lr, b1, b2, eps = opt
    value, grads = jax.value_and_grad(loss)(params, model, x, src, dst,
                                            labels, num_nodes, rows,
                                            precision)
    t = t + 1
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v,
                               grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * (a / bc1) / (jnp.sqrt(b / bc2) + eps),
        params, m, v)
    return params, m, v, t, value, grads


def train(cfg: dict, graph, seed: int, *, steps: int = 3,
          precision: str = "highest", rows: int | None = None) -> dict:
    """The first ``steps`` Adam steps from the seed's weights.

    Returns the loss of each step, the norm of each leaf's first gradient
    and of each leaf's change over the steps."""
    opt = (float(cfg["lr"]), float(cfg["adam_b1"]), float(cfg["adam_b2"]),
           float(cfg["adam_eps"]))
    n = graph.num_nodes
    p0 = init_params(cfg, seed)
    x, src, dst, labels = (jnp.asarray(a) for a in
                           (graph.x, graph.src, graph.dst, graph.labels))
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    p, m, v, t = p0, zeros, zeros, jnp.float32(0)
    losses, first = [], None
    for _ in range(steps):
        p, m, v, t, value, grads = _adam_step(
            p, m, v, t, x, src, dst, labels, model=cfg["model"],
            num_nodes=n, rows=n if rows is None else rows, opt=opt,
            precision=precision)
        losses.append(float(value))
        if first is None:
            first = leaf_norms(grads)
    delta = jax.tree_util.tree_map(lambda a, b: a - b, p, p0)
    return {"losses": losses, "grad_norms": first,
            "delta_norms": leaf_norms(delta)}
