"""Multi-device checks, run in a subprocess with 8 host devices
(tests/test_distributed.py drives this)."""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS
from jax import shard_map

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.distributed import collectives, pipeline, sharding as shd, step as steplib  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.optim import adamw  # noqa: E402


def check_ring_allreduce():
    mesh = jax.make_mesh((8,), ("r",))
    x = jnp.arange(8 * 16 * 4, dtype=jnp.float32).reshape(8, 16, 4)

    def ring(xl):
        return collectives.ring_allreduce(xl[0], "r")

    got = shard_map(ring, mesh=mesh, in_specs=PS("r"), out_specs=PS("r"))(x)
    want = jnp.tile(jnp.sum(x, 0, keepdims=True) / 1.0, (8, 1, 1))[:, : 16 // 8]
    # out_specs PS("r") splits the replicated result; compare against psum
    def psum_ref(xl):
        return jax.lax.psum(xl[0], "r")
    want2 = shard_map(psum_ref, mesh=mesh, in_specs=PS("r"),
                      out_specs=PS("r"))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want2),
                               rtol=1e-5)
    print("ring_allreduce OK")


def check_ring_matmul():
    mesh = jax.make_mesh((8,), ("model",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 64)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((64, 24)).astype(np.float32))
    fn = collectives.make_ring_matmul(mesh, "model")
    got = fn(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-4)
    print("ring_matmul OK")


def check_hierarchical_and_compressed_psum():
    mesh = jax.make_mesh((2, 4), ("pod", "data"))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 4, 16, 8)).astype(np.float32))

    def h(xl):
        return collectives.hierarchical_psum(xl[0, 0], "pod", "data")

    got = shard_map(h, mesh=mesh, in_specs=PS("pod", "data"),
                    out_specs=PS("pod", "data"))(x)

    def p(xl):
        return jax.lax.psum(jax.lax.psum(xl[0, 0], "data"), "pod")

    want = shard_map(p, mesh=mesh, in_specs=PS("pod", "data"),
                     out_specs=PS("pod", "data"))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)

    def c(xl):
        # error-feedback buffer lives at the reduce-scattered shape
        ef = jnp.zeros((xl.shape[2] // 4, xl.shape[3]), jnp.float32)
        out, new_ef = collectives.compressed_psum(xl[0, 0], ef, "pod", "data")
        return out

    got_c = shard_map(c, mesh=mesh, in_specs=PS("pod", "data"),
                      out_specs=PS("pod", "data"))(x)
    err = np.max(np.abs(np.asarray(got_c) - np.asarray(want)))
    scale = np.max(np.abs(np.asarray(want)))
    assert err < 0.05 * scale + 0.05, (err, scale)
    print("hierarchical/compressed psum OK (int8 err %.4f)" % err)


def check_pipeline():
    mesh = jax.make_mesh((4,), ("pipe",))
    rng = np.random.default_rng(2)
    n_stages, n_micro, dim = 4, 8, 16
    ws = jnp.asarray(rng.standard_normal((n_stages, dim, dim))
                     .astype(np.float32) * 0.3)

    def stage(w, x):
        return jnp.tanh(x @ w)

    xm = jnp.asarray(rng.standard_normal((n_micro, 4, dim)).astype(np.float32))
    got = pipeline.pipeline_forward(stage, ws, xm, mesh=mesh, axis="pipe")
    want = xm
    for i in range(n_stages):
        want = jnp.tanh(want @ ws[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    print("pipeline OK")


def check_pjit_train_step_matches_single_device():
    cfg = ModelConfig("t", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
                      vocab_size=128, dtype="float32", max_seq=64)
    prm = lm.init(jax.random.PRNGKey(0), cfg)
    ts = steplib.TrainStepConfig(opt=adamw.AdamWConfig(lr=1e-3),
                                 remat_policy="none")
    opt = adamw.init(prm, ts.opt)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 128)
    batch = {"tokens": toks, "labels": toks}

    # single device
    def loss(p):
        return lm.loss_fn(p, cfg, batch, remat_policy="none")
    (l0, _), g = jax.value_and_grad(loss, has_aux=True)(prm)
    p1, o1, m1 = adamw.update(g, opt, prm, ts.opt,
                              lr_scale=jnp.asarray(0.0, jnp.float32))

    # 2×2 mesh pjit
    mesh = make_host_mesh(2, 2)
    plan = shd.ParallelPlan.for_mesh(mesh)
    fn, shardings_for = steplib.build_train_step(cfg, mesh, plan, ts)
    in_sh, _ = shardings_for(prm, opt, {"tokens": (4, 16), "labels": (4, 16)})
    with mesh:
        p2, o2, m2 = jax.jit(fn, in_shardings=in_sh)(
            prm, opt, batch, jnp.asarray(0, jnp.int32))
    np.testing.assert_allclose(float(l0), float(m2["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)
    print("pjit train step == single device OK (loss %.4f)" % float(m2["loss"]))


def check_serve_step_sharded():
    cfg = ModelConfig("t", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
                      vocab_size=128, dtype="float32", max_seq=64)
    prm = lm.init(jax.random.PRNGKey(0), cfg)
    mesh = make_host_mesh(2, 2)
    plan = shd.ParallelPlan.for_mesh(mesh)
    fn, shardings_for = steplib.build_serve_step(cfg, mesh, plan, 4, 16)
    psh, tok_sh, st_sh = shardings_for(prm)
    state = lm.init_decode_state(cfg, 4, 16, jnp.float32)
    tok = jax.random.randint(jax.random.PRNGKey(2), (4, 1), 0, 128)
    with mesh:
        lg, st = jax.jit(fn, in_shardings=(psh, tok_sh, st_sh))(prm, tok, state)
    lg1, st1 = lm.decode_step(prm, cfg, tok, state)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg1), rtol=2e-3,
                               atol=2e-3)
    print("sharded serve step == single device OK")


def check_moe_shard_map_parity():
    """EP shard_map MoE (§Perf #5) ≡ global capacity path, fwd and grads."""
    from repro.models import moe as moe_lib
    cfg = ModelConfig("t", family="moe", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
                      vocab_size=128, num_experts=8, top_k=2, moe_d_ff=16,
                      capacity_factor=8.0, dtype="float32", max_seq=64)
    prm = moe_lib.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    y_ref, _ = moe_lib.moe_capacity(prm, x, cfg)
    mesh = make_host_mesh(2, 4)
    plan = shd.ParallelPlan.for_mesh(mesh)
    with mesh, shd.activation_sharding(mesh, plan):
        y_sm, _ = jax.jit(lambda p, x: moe_lib.moe_shard_map(p, x, cfg))(prm, x)
    np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)

    def loss_sm(p, x):
        with shd.activation_sharding(mesh, plan):
            y, _ = moe_lib.moe_shard_map(p, x, cfg)
        return jnp.sum(y ** 2)

    def loss_ref(p, x):
        y, _ = moe_lib.moe_capacity(p, x, cfg)
        return jnp.sum(y ** 2)

    with mesh:
        g_sm = jax.jit(jax.grad(loss_sm, argnums=(0, 1)))(prm, x)
    g_ref = jax.grad(loss_ref, argnums=(0, 1))(prm, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_sm),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)
    print("moe shard_map parity OK")


def check_tp_out_project_parity():
    """Opt-in hand-scheduled TP projection ≡ plain matmul (kept for real-TPU
    bf16-wire all-reduces; §Perf log #6)."""
    from repro.models import layers as L
    from repro.models.params import P
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((8, 16, 32)).astype(np.float32))
    w = P(jnp.asarray(rng.standard_normal((32, 24)).astype(np.float32)),
          ("heads", "embed"))
    mesh = make_host_mesh(2, 4)
    plan = shd.ParallelPlan.for_mesh(mesh)
    want = x @ w.value
    with mesh, shd.activation_sharding(mesh, plan):
        got = jax.jit(lambda x, wv: L.tp_out_project(x, P(wv, w.axes)))(
            x, w.value)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    print("tp_out_project parity OK")


def check_elastic_reshard():
    """Elastic scaling drill: checkpoint written under mesh A (2×4) restores
    onto mesh B (4×2) — the restart path after losing/gaining nodes."""
    import tempfile
    from repro.checkpoint import checkpoint as ckpt
    cfg = ModelConfig("t", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
                      vocab_size=128, dtype="float32", max_seq=64)
    prm = lm.init(jax.random.PRNGKey(0), cfg)
    mesh_a = make_host_mesh(2, 4)
    plan_a = shd.ParallelPlan.for_mesh(mesh_a)
    sh_a = shd.param_shardings(prm, plan_a, mesh_a)
    prm_a = jax.device_put(prm, sh_a)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(prm_a, d, 42)
        mesh_b = make_host_mesh(4, 2)
        plan_b = shd.ParallelPlan.for_mesh(mesh_b)
        sh_b = shd.param_shardings(prm, plan_b, mesh_b)
        prm_b = ckpt.restore(prm, d, shardings=sh_b)
    for a, b in zip(jax.tree_util.tree_leaves(prm),
                    jax.tree_util.tree_leaves(prm_b)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))
    print("elastic reshard (2×4 → 4×2) OK")


if __name__ == "__main__":
    check_ring_allreduce()
    check_ring_matmul()
    check_hierarchical_and_compressed_psum()
    check_pipeline()
    check_pjit_train_step_matches_single_device()
    check_serve_step_sharded()
    check_moe_shard_map_parity()
    check_tp_out_project_parity()
    check_elastic_reshard()
    print("ALL DISTRIBUTED CHECKS OK")
