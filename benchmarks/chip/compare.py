"""The comparison that decides ``correct``: the numbers that put what the
timed path produced beside the plain reference, and their limits.

Training (the first three steps of the window's own trainer):

* ``loss_gap``   — over the three steps, the largest |L − L_ref| / |L_ref|;
* ``grad_gap``   — over the leaves, the largest gap between the norm of the
  first gradient as the optimizer got it (read back from Adam's first
  moment after one step) and the reference's, over the larger of that
  leaf's reference norm and the median leaf's;
* ``update_gap`` — the same for each leaf's change over the three steps,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (Adam moves those by round-off alone).

Each cell's limits live in ``limits/<cell>.json``, with the readings they
were set from in PERF.md.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
GRAD_FLOOR = 1e-3          # of the median leaf's reference gradient


def load_limits(cell: str, directory: Path = HERE / "limits") -> dict:
    path = directory / f"{cell}.json"
    if not path.exists():
        raise FileNotFoundError(f"no limits for cell {cell!r} at {path}")
    return {k: float(v) for k, v in json.loads(path.read_text()).items()
            if not k.startswith("_")}


def leaf_norms(tree) -> dict:
    """``{"<layer>.<name>": float64 norm}`` of a list-of-dicts tree."""
    return {f"{i}.{k}": float(np.linalg.norm(np.asarray(a, np.float64)))
            for i, layer in enumerate(tree) for k, a in layer.items()}


def _leaf_gaps(got: dict, ref: dict, keep=None) -> float:
    names = [k for k in ref if keep is None or k in keep]
    if set(got) != set(ref):
        raise ValueError(f"leaves differ: {sorted(got)} vs {sorted(ref)}")
    median = float(np.median([ref[k] for k in ref]))
    return max((abs(got[k] - ref[k]) / max(ref[k], median, 1e-30)
                for k in names), default=0.0)


def train_numbers(got: dict, ref: dict) -> dict:
    """``got`` and ``ref`` as a cell's reference ``train`` returns them
    (``losses``, ``grad_norms``, ``delta_norms``)."""
    losses = [abs(a - b) / max(abs(b), 1e-30)
              for a, b in zip(got["losses"], ref["losses"], strict=True)]
    g_ref = ref["grad_norms"]
    median = float(np.median(list(g_ref.values())))
    moved = {k for k, v in g_ref.items() if v >= GRAD_FLOOR * median}
    return {"loss_gap": max(losses),
            "grad_gap": _leaf_gaps(got["grad_norms"], g_ref),
            "update_gap": _leaf_gaps(got["delta_norms"], ref["delta_norms"],
                                     keep=moved)}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, ``{name: {"value", "limit"}}``). A number that is missing
    or not finite fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok &= bool(good)
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
