"""Pallas kernels vs pure-jnp oracles (interpret=True): shape/dtype sweeps
per kernel, as required for every kernel in kernels/."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config_space import KernelConfig
from repro.kernels import ops as kops, ref

RNG = np.random.default_rng(7)

SHAPES = [(260, 40, 17), (1000, 100, 32), (64, 64, 1), (512, 3, 130),
          (130, 128, 64)]
DTYPES = [np.float32, jnp.bfloat16]
SCHEDS = ["PR", "SR"]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("m,s,n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("sched", SCHEDS)
def test_segment_reduce_kernel(m, s, n, dtype, sched):
    idx = np.sort(RNG.integers(0, s, m)).astype(np.int32)
    x = jnp.asarray(RNG.standard_normal((m, n)), dtype)
    cfg = KernelConfig(sched, 64, 128, 128, 8)
    got = kops.segment_reduce(x, jnp.asarray(idx), s, "sum", cfg,
                              interpret=True)
    want = ref.segment_reduce(x.astype(jnp.float32), jnp.asarray(idx), s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("reduce", ["mean", "max"])
def test_segment_reduce_kernel_mean_max(reduce):
    m, s, n = 300, 37, 24
    idx = np.sort(RNG.integers(0, s, m)).astype(np.int32)
    x = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    got = kops.segment_reduce(x, jnp.asarray(idx), s, reduce,
                              KernelConfig("SR", 64, 128, 64, 1),
                              interpret=True)
    want = ref.segment_reduce(x, jnp.asarray(idx), s, reduce)
    ga, wa = np.asarray(got), np.asarray(want)
    mask = np.isfinite(wa)
    assert np.array_equal(np.isfinite(ga), mask)
    np.testing.assert_allclose(ga[mask], wa[mask], rtol=3e-4, atol=3e-4)


def test_segment_reduce_kernel_empty_segments():
    """Gapped ids: many empty segments between occupied ones."""
    m, s = 200, 500
    idx = np.sort(RNG.choice(np.arange(0, s, 7), m)).astype(np.int32)
    x = jnp.asarray(RNG.standard_normal((m, 16)), jnp.float32)
    for sched in SCHEDS:
        got = kops.segment_reduce(x, jnp.asarray(idx), s, "sum",
                                  KernelConfig(sched, 64, 128, 64, 8),
                                  interpret=True)
        want = ref.segment_reduce(x, jnp.asarray(idx), s)
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("reduce", ["sum", "mean", "max"])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("sched", SCHEDS)
def test_gather_segment_reduce_kernel(reduce, weighted, sched):
    """Every reduce × weighted combo is a single fused launch (PR + max
    falls back to the SR walk inside the kernel)."""
    m, v, s, n = 400, 90, 60, 20
    seg = np.sort(RNG.integers(0, s, m)).astype(np.int32)
    gidx = RNG.integers(0, v, m).astype(np.int32)
    w = jnp.asarray(RNG.standard_normal(m), jnp.float32) if weighted else None
    h = jnp.asarray(RNG.standard_normal((v, n)), jnp.float32)
    cfg = KernelConfig(sched, 64, 128, 128, 8)
    got = kops.gather_segment_reduce(h, jnp.asarray(gidx), jnp.asarray(seg),
                                     s, weight=w, reduce=reduce, config=cfg,
                                     interpret=True)
    want = ref.gather_segment_reduce(h, jnp.asarray(gidx), jnp.asarray(seg),
                                     s, weight=w, reduce=reduce)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_gather_segment_reduce_kernel_mean_max_gapped():
    """Gapped/empty segments: mean divides only live segments (empty → 0),
    max keeps the segment_max identity (-inf) on empty ones."""
    m, v, s, n = 200, 50, 500, 12
    seg = np.sort(RNG.choice(np.arange(0, s, 7), m)).astype(np.int32)
    gidx = RNG.integers(0, v, m).astype(np.int32)
    h = jnp.asarray(RNG.standard_normal((v, n)), jnp.float32)
    cfg = KernelConfig("SR", 64, 128, 64, 1)
    for reduce in ("mean", "max"):
        got = kops.gather_segment_reduce(h, jnp.asarray(gidx),
                                         jnp.asarray(seg), s, reduce=reduce,
                                         config=cfg, interpret=True)
        want = ref.gather_segment_reduce(h, jnp.asarray(gidx),
                                         jnp.asarray(seg), s, reduce=reduce)
        ga, wa = np.asarray(got), np.asarray(want)
        mask = np.isfinite(wa)
        assert np.array_equal(np.isfinite(ga), mask)
        np.testing.assert_allclose(ga[mask], wa[mask], rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# the flat grid of the gather and fused kernels: only owned (block, chunk)
# steps are walked, at s_b = 64 and m_b = 128 here
# ---------------------------------------------------------------------------

FLAT_CASES = ["hub", "hub_tail", "empty_runs", "gapped", "ragged", "short",
              "none"]


def _flat_case(case, rng):
    """(sorted segment ids, num_segments) on which the step tables are
    easy to get wrong."""
    if case == "hub":                    # one segment owns every chunk
        return np.full(1000, 70, np.int32), 300
    ids, s = {
        # a hub beside many short segments
        "hub_tail": lambda: (np.concatenate([np.full(700, 5),
                                             rng.integers(0, 300, 300)]),
                             300),
        # blocks 1-3 own nothing
        "empty_runs": lambda: (np.concatenate([rng.integers(0, 40, 300),
                                               rng.integers(250, 300, 300)]),
                               300),
        "gapped": lambda: (rng.choice(np.arange(0, 500, 7), 200), 500),
        "ragged": lambda: (rng.integers(0, 130, 400), 130),  # 130 % 64 != 0
        "short": lambda: (rng.integers(0, 60, 50), 60),      # E < m_b
        "none": lambda: (np.zeros(0, np.int32), 70),         # E = 0
    }[case]()
    return np.sort(ids).astype(np.int32), s


def _random_index(rng):
    """Sorted ids whose skew ranges from one hub to uniform, at a random
    tiling."""
    s = int(rng.integers(1, 400))
    m = int(rng.integers(0, 3000))
    p = rng.dirichlet(np.full(s, rng.choice([0.02, 1.0, 30.0])))
    ids = np.sort(rng.choice(s, m, p=p)).astype(np.int32)
    return ids, s, int(rng.choice([8, 64, 128])), int(rng.choice([8, 128,
                                                                  512]))


def _check_step_table(idx, s, s_b, m_b):
    from repro.kernels.segment_reduce import (chunk_metadata,
                                              flat_grid_steps, flat_row,
                                              flat_step, step_table)
    m_pad = max(-(-len(idx) // m_b), 1) * m_b
    n_chunks = m_pad // m_b
    idxp = np.full(m_pad, s, np.int32)
    idxp[:len(idx)] = idx
    cf, cc = (np.asarray(a) for a in chunk_metadata(jnp.asarray(idxp), s,
                                                     s_b, m_b, m_pad))
    ob = len(cc)
    want = [(b, int(cf[b]) + i) for b in range(ob) for i in range(cc[b])]
    for max_chunks in {max(1, int(cc.max())), n_chunks}:   # tight, pinned
        t_len = flat_grid_steps(n_chunks, ob, max_chunks)
        assert t_len <= min(n_chunks + ob - 1, ob * max_chunks)
        blk, start = (np.asarray(a) for a in step_table(jnp.asarray(cc),
                                                        t_len))
        t = np.arange(t_len)
        b, k = (np.asarray(a) for a in flat_step(t, blk, start))
        row = np.asarray(flat_row(t, cf, cc, blk, start))
        assert np.all((b >= 0) & (b < ob) & (k >= 0))
        owned = k < cc[b]
        empty = ~owned & (k == 0)
        inert = np.flatnonzero(~owned & ~empty)
        # block-major, chunk-increasing, each owned pair exactly once
        assert list(zip(b[owned].tolist(), row[owned].tolist())) == want
        # one step for each block that owns nothing
        assert b[empty].tolist() == np.flatnonzero(cc == 0).tolist()
        # inert steps only at the end, re-reading the last block's last row
        assert inert.tolist() == list(range(t_len - len(inert), t_len))
        assert np.all(b[inert] == ob - 1)
        assert np.all(row[inert] == cf[-1] + max(cc[-1] - 1, 0))


@pytest.mark.parametrize("case", FLAT_CASES + ["random"])
def test_flat_step_table_covers_each_owned_step_once(case):
    rng = np.random.default_rng((FLAT_CASES + ["random"]).index(case))
    if case == "random":
        draws = [_random_index(rng) for _ in range(15)]
    else:
        draws = [_flat_case(case, rng) + (64, 128)]
    for idx, s, s_b, m_b in draws:
        _check_step_table(idx, s, s_b, m_b)


def _flat_inputs(case, n=20, v=90):
    rng = np.random.default_rng(FLAT_CASES.index(case))
    idx, s = _flat_case(case, rng)
    m = len(idx)
    return (jnp.asarray(idx), s,
            jnp.asarray(rng.integers(0, v, m).astype(np.int32)),
            jnp.asarray(rng.standard_normal((v, n)), jnp.float32),
            jnp.asarray(rng.standard_normal(m), jnp.float32))


def _assert_close_where_finite(got, want, tol):
    ga, wa = np.asarray(got, np.float32), np.asarray(want, np.float32)
    mask = np.isfinite(wa)                  # max keeps -inf on empty ones
    assert np.array_equal(np.isfinite(ga), mask)
    np.testing.assert_allclose(ga[mask], wa[mask], **tol)


@pytest.mark.parametrize("case", FLAT_CASES)
@pytest.mark.parametrize("sched", SCHEDS)
@pytest.mark.parametrize("reduce", ["sum", "mean", "max"])
def test_gather_segment_reduce_flat_grid(case, sched, reduce):
    """Skewed, gapped, ragged, short and empty indices against the oracle,
    weighted or not in fp32 and bf16; a plan pinned to the worst case
    gives the tight plan's output bit for bit."""
    from repro.core.plan import make_plan
    seg, s, gidx, h32, w32 = _flat_inputs(case)
    plan = make_plan(np.asarray(seg), s, feat=h32.shape[1],
                     config=KernelConfig(sched, 64, 128, 128, 8))
    # weighted in fp32 on every other case, in bf16 on the rest
    odd = FLAT_CASES.index(case) % 2
    for dtype, weighted in ((np.float32, not odd), (jnp.bfloat16, odd)):
        h, w = h32.astype(dtype), w32.astype(dtype) if weighted else None
        got = kops.gather_segment_reduce(h, gidx, seg, s, weight=w,
                                         reduce=reduce, plan=plan,
                                         interpret=True)
        want = ref.gather_segment_reduce(
            h.astype(jnp.float32), gidx, seg, s,
            weight=None if w is None else w.astype(jnp.float32),
            reduce=reduce)
        _assert_close_where_finite(got, want, _tol(dtype))
        if dtype == np.float32:
            pinned = kops.gather_segment_reduce(
                h, gidx, seg, s, weight=w, reduce=reduce,
                plan=plan.pin_worst_case(), interpret=True)
            np.testing.assert_array_equal(np.asarray(pinned),
                                          np.asarray(got))


@pytest.mark.parametrize("case", FLAT_CASES)
@pytest.mark.parametrize("reduce", ["sum", "mean"])
def test_fused_transform_reduce_flat_grid(case, reduce):
    """The fused kernel on the same indices: every block, empty ones
    included, gets its transform; pinned and tight plans agree bit for
    bit."""
    from repro.core.plan import make_plan
    seg, s, gidx, h, w = _flat_inputs(case)
    wm = jnp.asarray(np.random.default_rng(3).standard_normal((20, 24)),
                     jnp.float32)
    plan = make_plan(np.asarray(seg), s, feat=h.shape[1],
                     config=KernelConfig("PR", 64, 128, 128, 8))
    got = kops.fused_transform_reduce(h, wm, gidx, seg, s, weight=w,
                                      reduce=reduce, plan=plan,
                                      interpret=True)
    want = ref.gather_segment_reduce(h, gidx, seg, s, weight=w,
                                     reduce=reduce) @ wm
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(np.float32))
    pinned = kops.fused_transform_reduce(h, wm, gidx, seg, s, weight=w,
                                         reduce=reduce,
                                         plan=plan.pin_worst_case(),
                                         interpret=True)
    np.testing.assert_array_equal(np.asarray(pinned), np.asarray(got))


def test_gather_segment_reduce_rejects_unknown_reduce():
    h = jnp.zeros((4, 8))
    idx = jnp.zeros(4, jnp.int32)
    with pytest.raises(ValueError):
        kops.gather_segment_reduce(h, idx, idx, 4, reduce="prod",
                                   interpret=True)


@pytest.mark.parametrize("m,k,n,e", [(130, 16, 16, 3), (300, 64, 48, 4),
                                     (512, 32, 130, 7), (96, 8, 8, 96)])
def test_segment_matmul_kernel(m, k, n, e):
    sizes = RNG.multinomial(m, np.ones(e) / e).astype(np.int32)
    x = jnp.asarray(RNG.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((e, k, n)), jnp.float32)
    got = kops.segment_matmul(x, jnp.asarray(sizes), w, interpret=True)
    want = ref.segment_matmul(x, jnp.asarray(sizes), w)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_segment_matmul_kernel_empty_groups():
    m, k, n, e = 128, 8, 8, 6
    sizes = np.array([0, 64, 0, 0, 64, 0], np.int32)
    x = jnp.asarray(RNG.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((e, k, n)), jnp.float32)
    got = kops.segment_matmul(x, jnp.asarray(sizes), w, interpret=True)
    want = ref.segment_matmul(x, jnp.asarray(sizes), w)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("ra,rb,m,n", [(40, 60, 300, 16), (100, 100, 513, 64),
                                       (20, 30, 64, 130)])
def test_sddmm_kernel(ra, rb, m, n):
    """SDDMM (paper §VI — the SpMM backward) vs the per-edge-dot oracle."""
    from repro.core import ops as core_ops
    a = jnp.asarray(RNG.standard_normal((ra, n)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((rb, n)), jnp.float32)
    ri = jnp.asarray(RNG.integers(0, ra, m).astype(np.int32))
    ci = jnp.asarray(RNG.integers(0, rb, m).astype(np.int32))
    got = kops.sddmm(a, b, ri, ci, interpret=True)
    want = core_ops.sddmm(a, b, ri, ci)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("h_dim", [None, 1, 4])
def test_segment_softmax_kernel(h_dim):
    """Fused single-launch softmax vs the three-pass jnp oracle, 1-D and
    multi-head logits."""
    from repro.core.ops import _segment_softmax_ref
    m, s = 300, 40
    idx = np.sort(RNG.integers(0, s, m)).astype(np.int32)
    shape = (m,) if h_dim is None else (m, h_dim)
    x = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    got = kops.segment_softmax(x, jnp.asarray(idx), s,
                               config=KernelConfig("SR", 64, 128, 64, 1),
                               interpret=True)
    want = _segment_softmax_ref(x, jnp.asarray(idx), s)
    assert got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_sddmm_accepts_plan_for_config():
    """plan= supplies the tiling config only (API symmetry with
    segment_matmul) — results are identical to the explicit-config call."""
    from repro.core import ops as core_ops
    from repro.core.plan import make_plan
    m, r, n = 300, 40, 16
    seg = np.sort(RNG.integers(0, 30, m)).astype(np.int32)
    plan = make_plan(seg, 30, feat=n, config=KernelConfig("SR", 64, 128, 64, 1))
    a = jnp.asarray(RNG.standard_normal((r, n)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((r, n)), jnp.float32)
    ri = jnp.asarray(RNG.integers(0, r, m).astype(np.int32))
    ci = jnp.asarray(RNG.integers(0, r, m).astype(np.int32))
    got = kops.sddmm(a, b, ri, ci, plan=plan, interpret=True)
    explicit = kops.sddmm(a, b, ri, ci, config=plan.config, interpret=True)
    want = core_ops.sddmm(a, b, ri, ci)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(explicit))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_kernel_respects_generated_rules():
    """config=None routes through the data-aware generated rules."""
    m, s, n = 500, 50, 8
    idx = np.sort(RNG.integers(0, s, m)).astype(np.int32)
    x = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    got = kops.segment_reduce(x, jnp.asarray(idx), s, interpret=True)
    want = ref.segment_reduce(x, jnp.asarray(idx), s)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)
