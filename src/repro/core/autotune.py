"""Wall-clock autotuner + persistent performance database (paper §III-C).

This closes the measurement loop the analytical pipeline left open:
``repro.core.perfdb`` scores configs with the v5e roofline model, whereas the
paper's perf database is *measured* — the pruned config space is swept with
real kernel executions and the winners are what the decision-tree rules are
distilled from (Fig. 5). Here:

* :func:`tune` sweeps the pruned lattice (``config_space.enumerate_configs``)
  by timing the actual kernels — Pallas interpret on CPU, Mosaic on TPU —
  with warmup + ``jax.block_until_ready`` and a median-of-k timer over
  deterministic synthetic inputs (seeded; CI-stable);
* :class:`PerfDB` persists every sweep as JSON under ``~/.cache/repro-perfdb``
  (override with ``REPRO_PERFDB_PATH``), keyed by
  ``backend / op / quantized InputFeatures`` — a (device, shape-class) is
  tuned **once** and the measured config is reused forever;
* the cached winner becomes the *top tier* of the selection precedence
  (:func:`repro.core.heuristics.select_config`):

      explicit ``config=``  >  measured (``tune=True`` / ``REPRO_AUTOTUNE=1``)
      >  generated decision-tree rules  >  hand-crafted static rule

* ``python -m repro.core.train_rules --from-perfdb <path>`` re-distills
  ``_generated_rules.py`` from the measured records, replacing the
  analytical evaluate_fn with wall-clock truth.

Environment knobs: ``REPRO_AUTOTUNE`` (enable the measured tier globally),
``REPRO_PERFDB_PATH`` (cache directory or ``*.json`` file),
``REPRO_AUTOTUNE_MAX_CONFIGS`` / ``REPRO_AUTOTUNE_REPS`` (sweep budget).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import pathlib
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.config_space import KernelConfig, all_configs
from repro.core.features import InputFeatures

__all__ = ["PerfDB", "TuneResult", "tune", "autotune_enabled", "perf_key",
           "quantize_features"]

DB_VERSION = 1
DEFAULT_MAX_CONFIGS = 24
DEFAULT_REPS = 5
DEFAULT_WARMUP = 2
DEFAULT_SEED = 0                 # deterministic synthetic inputs (de-flake)
_QUANT_STEP = 0.5                # log2-space bin width for shape classes


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "") not in ("", "0", "false", "False")


def autotune_enabled() -> bool:
    """True when ``REPRO_AUTOTUNE=1`` turns on the measured tier globally."""
    return _env_flag("REPRO_AUTOTUNE")


# ---------------------------------------------------------------------------
# shape-class keys
# ---------------------------------------------------------------------------

def quantize_features(feats: InputFeatures,
                      step: float = _QUANT_STEP) -> Tuple[float, ...]:
    """Quantize the log2 feature vector to ``step``-wide bins.

    Shapes within the same bin share one tuned config — the paper's
    augmentation (×60 noised/scaled variants per dataset) exists precisely
    because nearby shapes want the same schedule; binning is the inverse
    move: nearby shapes *reuse* one measurement."""
    vec = feats.as_vector()
    # + 0.0 normalizes IEEE -0.0 to +0.0 so one bin maps to one cache key
    return tuple(float(np.round(v / step) * step + 0.0) for v in vec)


def perf_key(backend: str, op: str, feats: InputFeatures) -> str:
    """``backend / op [@io-dtype shelf] / quantized shape class``.

    The io dtype is a separate *shelf*, not a tree feature: fp32 keys keep
    their historical format (warm caches stay warm) and lowered-precision
    sweeps land next to them under ``op@b2`` without retraining the
    decision tree's 3-D feature vector."""
    q = quantize_features(feats)
    shelf = "" if feats.dtype_bytes == 4 else f"@b{feats.dtype_bytes}"
    return f"{backend}/{op}{shelf}/" + ",".join(f"{v:g}" for v in q)


# ---------------------------------------------------------------------------
# persistent database
# ---------------------------------------------------------------------------

class PerfDB:
    """On-disk JSON cache of measured sweeps, one entry per shape class.

    The whole sweep is stored (config → median µs), not just the winner, so
    ``train_rules --from-perfdb`` can retrain the decision tree from the same
    records and the ablation benchmark can read baseline-config timings
    without re-measuring."""

    def __init__(self, path: "str | os.PathLike | None" = None):
        if path is None:
            path = os.environ.get("REPRO_PERFDB_PATH") or os.path.join(
                os.path.expanduser("~"), ".cache", "repro-perfdb")
        p = pathlib.Path(path)
        self.file = p if p.suffix == ".json" else p / "perfdb.json"
        self._entries: Optional[Dict[str, dict]] = None

    # -- I/O ---------------------------------------------------------------
    def load(self) -> Dict[str, dict]:
        if self._entries is None:
            try:
                with open(self.file) as f:
                    doc = json.load(f)
                self._entries = (doc.get("entries", {})
                                 if doc.get("version") == DB_VERSION else {})
            except (OSError, ValueError):
                self._entries = {}
        return self._entries

    def _save(self) -> None:
        self.file.parent.mkdir(parents=True, exist_ok=True)
        # merge over the current on-disk state so concurrent writers only
        # ever lose per-key races, never whole entries written by others
        on_disk: Dict[str, dict] = {}
        try:
            with open(self.file) as f:
                doc = json.load(f)
            if doc.get("version") == DB_VERSION:
                on_disk = doc.get("entries", {})
        except (OSError, ValueError):
            pass
        on_disk.update(self._entries)
        self._entries = on_disk
        doc = {"version": DB_VERSION, "entries": self._entries}
        # atomic replace: concurrent CI jobs never observe a torn file
        fd, tmp = tempfile.mkstemp(dir=self.file.parent, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, self.file)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- access ------------------------------------------------------------
    def get(self, key: str) -> Optional[dict]:
        return self.load().get(key)

    def put(self, key: str, entry: dict) -> None:
        self.load()[key] = entry
        self._save()

    def __len__(self) -> int:
        return len(self.load())

    def keys(self):
        return self.load().keys()


@functools.lru_cache(maxsize=8)
def _default_db(path_key: str) -> PerfDB:
    """Process-wide PerfDB per path (entries parsed once, not per op call)."""
    return PerfDB(path_key or None)


# ---------------------------------------------------------------------------
# measurement adapters (one per op)
# ---------------------------------------------------------------------------
# Each adapter builds deterministic synthetic inputs for a shape class and
# returns ``run(cfg) -> zero-arg jitted callable``; the tuner times it.

def _synth(idx_size: int, num_segments: int, feat: int, seed: int,
           dtype=np.float32):
    rng = np.random.default_rng(seed)
    idx = np.sort(rng.integers(0, max(num_segments, 1),
                               size=idx_size)).astype(np.int32)
    x = rng.standard_normal((idx_size, feat)).astype(np.float32)
    if np.dtype(dtype) != np.float32:
        import jax.numpy as jnp
        x = np.asarray(jnp.asarray(x).astype(dtype))
    return rng, idx, x


def _cast(arr, dtype):
    """Cast a synthetic fp32 numpy array to the sweep's io dtype."""
    import jax.numpy as jnp
    a = jnp.asarray(arr)
    return a if np.dtype(dtype) == np.float32 else a.astype(dtype)


def _runner_segment_reduce(idx_size, num_segments, feat, interpret, seed,
                           dtype=np.float32):
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    _, idx, x = _synth(idx_size, num_segments, feat, seed, dtype)
    xj, idxj = jnp.asarray(x), jnp.asarray(idx)

    def run(cfg: KernelConfig):
        return lambda: kops.segment_reduce(xj, idxj, num_segments,
                                           reduce="sum", config=cfg,
                                           interpret=interpret)
    return run


def _runner_gather_segment_reduce(idx_size, num_segments, feat, interpret,
                                  seed, reduce: str = "sum",
                                  dtype=np.float32):
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    rng, seg, _ = _synth(idx_size, num_segments, feat, seed)
    h = _cast(rng.standard_normal(
        (max(num_segments, 1), feat)).astype(np.float32), dtype)
    gather_idx = jnp.asarray(rng.integers(
        0, max(num_segments, 1), size=idx_size).astype(np.int32))
    segj = jnp.asarray(seg)

    def run(cfg: KernelConfig):
        return lambda: kops.gather_segment_reduce(h, gather_idx, segj,
                                                  num_segments, reduce=reduce,
                                                  config=cfg,
                                                  interpret=interpret)
    return run


def _runner_segment_softmax(idx_size, num_segments, feat, interpret, seed,
                            dtype=np.float32):
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    rng, seg, _ = _synth(idx_size, num_segments, feat, seed)
    x = _cast(rng.standard_normal(
        (idx_size, max(feat, 1))).astype(np.float32), dtype)
    segj = jnp.asarray(seg)

    def run(cfg: KernelConfig):
        return lambda: kops.segment_softmax(x, segj, num_segments, config=cfg,
                                            interpret=interpret)
    return run


def _runner_segment_matmul(idx_size, num_segments, feat, interpret, seed,
                           dtype=np.float32):
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    rng = np.random.default_rng(seed)
    e = max(num_segments, 1)
    sizes = np.full((e,), idx_size // e, np.int32)
    sizes[: idx_size - int(sizes.sum())] += 1
    x = _cast(rng.standard_normal((idx_size, feat)).astype(np.float32), dtype)
    w = _cast(rng.standard_normal((e, feat, feat)).astype(np.float32), dtype)
    gs = jnp.asarray(sizes)

    def run(cfg: KernelConfig):
        return lambda: kops.segment_matmul(x, gs, w, config=cfg,
                                           interpret=interpret)
    return run


def _runner_sddmm(idx_size, num_segments, feat, interpret, seed,
                  dtype=np.float32):
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    rng = np.random.default_rng(seed)
    r = max(num_segments, 1)
    a = _cast(rng.standard_normal((r, feat)).astype(np.float32), dtype)
    b = _cast(rng.standard_normal((r, feat)).astype(np.float32), dtype)
    row = jnp.asarray(rng.integers(0, r, size=idx_size).astype(np.int32))
    col = jnp.asarray(rng.integers(0, r, size=idx_size).astype(np.int32))

    def run(cfg: KernelConfig):
        return lambda: kops.sddmm(a, b, row, col, config=cfg,
                                  interpret=interpret)
    return run


def _runner_grouped_segment_matmul(idx_size, num_segments, feat, interpret,
                                   seed, dtype=np.float32):
    """The typed-edge profile of the grouped GEMM: zipf-skewed group sizes
    (most relations tiny, a few dominant — empty groups included), unlike
    :func:`_runner_segment_matmul`'s balanced MoE split. Same kernel,
    separately keyed PerfDB entries."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    rng = np.random.default_rng(seed)
    e = max(num_segments, 1)
    w_rel = np.minimum(rng.zipf(1.2, size=e).astype(np.float64),
                       max(idx_size / 2.0, 1.0))
    sizes = rng.multinomial(idx_size, w_rel / w_rel.sum()).astype(np.int32)
    x = _cast(rng.standard_normal((idx_size, feat)).astype(np.float32), dtype)
    w = _cast(rng.standard_normal((e, feat, feat)).astype(np.float32), dtype)
    gs = jnp.asarray(sizes)

    def run(cfg: KernelConfig):
        return lambda: kops.segment_matmul(x, gs, w, config=cfg,
                                           interpret=interpret)
    return run


def _runner_fused_transform_reduce(idx_size, num_segments, feat, interpret,
                                   seed, dtype=np.float32):
    """The one-launch SpMM+GEMM: gather → in-kernel (d_in, d_out) transform →
    reduce. Swept with a square weight (d_out = feat) like the matmul
    runners."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    rng, seg, _ = _synth(idx_size, num_segments, feat, seed)
    h = _cast(rng.standard_normal(
        (max(num_segments, 1), feat)).astype(np.float32), dtype)
    w = _cast(rng.standard_normal((feat, feat)).astype(np.float32), dtype)
    gather_idx = jnp.asarray(rng.integers(
        0, max(num_segments, 1), size=idx_size).astype(np.int32))
    segj = jnp.asarray(seg)

    def run(cfg: KernelConfig):
        return lambda: kops.fused_transform_reduce(
            h, w, gather_idx, segj, num_segments, reduce="sum",
            config=cfg, interpret=interpret)
    return run


_OPS: Dict[str, Callable] = {
    "segment_reduce": _runner_segment_reduce,
    "gather_segment_reduce": _runner_gather_segment_reduce,
    "gather_segment_reduce_mean": functools.partial(
        _runner_gather_segment_reduce, reduce="mean"),
    "gather_segment_reduce_max": functools.partial(
        _runner_gather_segment_reduce, reduce="max"),
    "segment_softmax": _runner_segment_softmax,
    "segment_matmul": _runner_segment_matmul,
    "grouped_segment_matmul": _runner_grouped_segment_matmul,
    "sddmm": _runner_sddmm,
    "fused_transform_reduce": _runner_fused_transform_reduce,
}

# ops that consume only a projection of the config sweep the projected space
# (deduped), not the full lattice
_PROJECTED_OPS = ("segment_matmul", "grouped_segment_matmul", "sddmm")


def config_projection(op: str, cfg: KernelConfig) -> Tuple:
    """The slice of the config an op actually consumes (dedupe key)."""
    if op in _PROJECTED_OPS:
        return ("m_b", cfg.m_b, "n_b", cfg.n_b)
    if op == "segment_softmax":
        # the softmax walk ignores schedule/N_b/K_c (heads are one lane tile)
        return ("s_b", cfg.s_b, "m_b", cfg.m_b)
    if op == "gather_segment_reduce_max":
        # max forces the SR walk, so PR lattice points alias their SR twin
        return ("SR", cfg.s_b, cfg.n_b, cfg.m_b, 1)
    if op == "fused_transform_reduce":
        # stages full-width d_in rows (no N_b feature tiling) and always
        # accumulates via the full one-hot matmul; only ⟨S_b, M_b⟩ matter
        return ("fused", cfg.s_b, cfg.m_b)
    return cfg.astuple()


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------

def _median_us(fn: Callable[[], object], reps: int, warmup: int) -> float:
    """Median-of-k wall clock of a jitted zero-arg callable, µs.

    Warmup absorbs compilation; ``block_until_ready`` pins the async
    dispatch; the median (not mean/min) is the de-flake guard the CI
    regression gate depends on."""
    import jax
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn())
    ts: List[float] = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


# ---------------------------------------------------------------------------
# candidate enumeration
# ---------------------------------------------------------------------------

def _candidates(op: str, idx_size: int, num_segments: int, feat: int,
                max_configs: int,
                extra: Sequence[KernelConfig]) -> List[KernelConfig]:
    """Pruned-lattice sweep order: heuristic seeds first, then an
    even spread of the lattice (schedule-interleaved so both SR and PR are
    always represented), deduped by the op's config projection and capped.

    Seeding with the generated-rules and hand-crafted picks guarantees the
    tuned winner is never *worse* than either baseline on the measured
    workload — argmin over a superset."""
    from repro.core.heuristics import hand_crafted_config, select_config
    seeds = [select_config(idx_size, num_segments, feat, tune=False),
             hand_crafted_config(idx_size, num_segments, feat)]
    seeds.extend(extra)

    lattice = all_configs(feat)
    sr = [c for c in lattice if c.schedule == "SR"]
    pr = [c for c in lattice if c.schedule == "PR"]
    budget = max(max_configs - len(seeds), 2)
    sr_sel = sr[:: max(1, len(sr) // max(budget // 2, 1))]
    pr_sel = pr[:: max(1, len(pr) // max(budget - budget // 2, 1))]
    interleaved: List[KernelConfig] = []
    for i in range(max(len(sr_sel), len(pr_sel))):
        if i < len(sr_sel):
            interleaved.append(sr_sel[i])
        if i < len(pr_sel):
            interleaved.append(pr_sel[i])

    out: List[KernelConfig] = []
    seen = set()
    for cfg in list(seeds) + interleaved:
        pk = config_projection(op, cfg)
        if pk in seen:
            continue
        seen.add(pk)
        out.append(cfg)
        if len(out) >= max_configs:
            break
    return out


# ---------------------------------------------------------------------------
# tune
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Outcome of one :func:`tune` call (fresh sweep or cache hit)."""
    op: str
    backend: str
    key: str
    config: KernelConfig                    # the measured winner
    timings: Dict[Tuple, float]             # projection -> median µs
    timings_performed: int                  # 0 on a warm-cache hit
    cache_hit: bool

    def time_of(self, cfg: KernelConfig) -> Optional[float]:
        """Measured µs of ``cfg`` in this sweep (None if it wasn't swept)."""
        return self.timings.get(config_projection(self.op, cfg))


def _entry_to_result(op: str, backend: str, key: str,
                     entry: dict) -> TuneResult:
    timings = {config_projection(op, KernelConfig(*t["config"])): t["us"]
               for t in entry["timings"]}
    return TuneResult(op=op, backend=backend, key=key,
                      config=KernelConfig(*entry["best"]),
                      timings=timings, timings_performed=0, cache_hit=True)


def tune(op: str = "segment_reduce", *, idx_size: int, num_segments: int,
         feat: int, db: Optional[PerfDB] = None,
         max_configs: Optional[int] = None, reps: Optional[int] = None,
         warmup: Optional[int] = None, interpret: Optional[bool] = None,
         extra_configs: Sequence[KernelConfig] = (), force: bool = False,
         seed: int = DEFAULT_SEED, io_dtype: str = "float32",
         measure_fn: Optional[Callable[[KernelConfig], float]] = None,
         ) -> TuneResult:
    """Measure the pruned config lattice for one (op, shape class); cache.

    Consults the :class:`PerfDB` first — a warm cache returns with
    ``timings_performed == 0`` (no kernel executions at all). On a miss,
    every candidate is timed (median-of-``reps`` with ``warmup`` discarded
    iterations over seed-deterministic synthetic inputs) and the sweep is
    persisted. ``measure_fn`` swaps the wall-clock timer for a callable
    ``cfg -> µs`` (tests; analytical what-ifs).
    """
    import jax

    if op not in _OPS:
        raise ValueError(f"unknown op {op!r}; tunable: {sorted(_OPS)}")
    backend = jax.default_backend()
    if interpret is None and measure_fn is None:
        # same resolution as the real op calls — the sweep must measure the
        # mode that will run
        from repro.kernels.ops import _default_interpret
        interpret = _default_interpret()
    from repro.core.config_space import io_dtype_bytes
    feats = InputFeatures(int(idx_size), int(num_segments), int(feat),
                          dtype_bytes=io_dtype_bytes(io_dtype))
    key = perf_key(backend, op, feats)
    if db is None:
        # one parsed snapshot per path for the life of the process — a
        # REPRO_AUTOTUNE=1 hot loop must not re-read the JSON per op call
        db = _default_db(os.environ.get("REPRO_PERFDB_PATH", ""))

    from repro import obs

    if not force:
        entry = db.get(key)
        if entry is not None:
            obs.record_tune(op, cache_hit=True, key=key, backend=backend)
            return _entry_to_result(op, backend, key, entry)

    if max_configs is None:
        max_configs = int(os.environ.get("REPRO_AUTOTUNE_MAX_CONFIGS",
                                         str(DEFAULT_MAX_CONFIGS)))
    reps = (int(os.environ.get("REPRO_AUTOTUNE_REPS", str(DEFAULT_REPS)))
            if reps is None else reps)
    warmup = DEFAULT_WARMUP if warmup is None else warmup

    cands = _candidates(op, int(idx_size), int(num_segments), int(feat),
                        max_configs, extra_configs)
    if measure_fn is None:
        run = _OPS[op](int(idx_size), int(num_segments), int(feat),
                       interpret, seed, dtype=io_dtype)

        def measure_fn(cfg: KernelConfig) -> float:
            return _median_us(run(cfg), reps, warmup)

    swept: List[Tuple[KernelConfig, float]] = []
    with obs.span("autotune.tune", op=op, key=key,
                  candidates=len(cands)):
        for cfg in cands:
            swept.append((cfg, float(measure_fn(cfg))))

    best_cfg, _ = min(swept, key=lambda cu: cu[1])
    entry = {
        "op": op,
        "backend": backend,
        "features": list(quantize_features(feats)),
        "idx_size": int(idx_size),
        "num_segments": int(num_segments),
        "feat": int(feat),
        "io_dtype": io_dtype,
        "reps": reps,
        "warmup": warmup,
        "seed": seed,
        "best": list(best_cfg.astuple()),
        "timings": [{"config": list(c.astuple()), "us": u}
                    for c, u in swept],
    }
    db.put(key, entry)
    obs.record_tune(op, cache_hit=False, timings=len(swept), key=key,
                    backend=backend, best=list(best_cfg.astuple()))
    timings = {config_projection(op, c): u for c, u in swept}
    return TuneResult(op=op, backend=backend, key=key, config=best_cfg,
                      timings=timings, timings_performed=len(swept),
                      cache_hit=False)
