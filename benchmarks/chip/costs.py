"""Operations and bytes that the work needs, from its shapes.

Two levels:

* per kernel launch (:func:`kernel_cost`): the least a launch of a Pallas
  kernel has to do for the call it was given — every edge multiplied and
  added once, every distinct source row read once, the index and weight
  streams read once, the output written once. Padding the kernel adds for
  its own layout (rows widened to a 128-lane tile, grid steps it skips) is
  not counted, so a share of the roofline shows that waste;
* per training step of GCN and GraphSAGE (:func:`train_step_flops`, with
  :func:`forward_flops`; ``references/gcn.py`` and ``sage.py`` hand it
  on, and another model's reference module brings its own): the model's
  matmuls and aggregation arithmetic, each counted once, in the cheaper of
  the two orders a layer may take; nothing recomputed.

:class:`LaunchRecorder` notes the logical shapes of each kernel launch while
a step is traced: the wrappers of ``repro.kernels.ops`` are called through
unchanged, and the record is made at trace time only.
"""
from __future__ import annotations

import contextlib
import dataclasses

from benchmarks.chip.peaks import Peaks

INDEX_BYTES = 4            # int32 gather and segment ids


@dataclasses.dataclass(frozen=True)
class Launch:
    """One kernel launch as the program called it."""
    kernel: str
    num_rows: int          # V: rows of the gathered operand (softmax: E)
    num_edges: int         # E
    num_segments: int      # S: output rows (softmax: segments)
    d_in: int              # width gathered and reduced (softmax: heads)
    d_out: int             # output width (== d_in without a transform)
    weighted: bool
    io_bytes: int          # bytes per value of the gathered operand


@dataclasses.dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def least_s(self, peaks: Peaks) -> float:
        """The larger of the compute and the memory bound."""
        return max(self.flops / peaks.flops_per_s,
                   self.bytes / peaks.hbm_bytes_per_s)


SOFTMAX_FLOPS = 5          # max, subtract, exp, sum, divide per element


def kernel_cost(launch: Launch) -> Cost:
    """The least work of one launch, by kernel:

    * ``gather_segment_reduce``: one multiply (weighted) and one add per
      edge and feature; the distinct source rows, the gather and segment
      ids (and the weights) read once, the output written once;
    * ``fused_transform_reduce``: the same, plus the transform's
      2 · S · d_in · d_out and its weight read once;
    * ``segment_softmax`` over E logits of H heads (``num_edges`` × d_in):
      a max-shifted softmax, one operation per element for each of the
      max, the subtraction, the exponential, the sum and the division
      (5 · E · H); the logits and the segment ids read once, the weights
      written once.

    Any other kernel raises ``KeyError``."""
    e, v, s = launch.num_edges, launch.num_rows, launch.num_segments
    d_in, d_out, b = launch.d_in, launch.d_out, launch.io_bytes
    if launch.kernel == "segment_softmax":
        return Cost(SOFTMAX_FLOPS * e * d_in,
                    e * d_in * b + e * INDEX_BYTES + e * d_out * b)
    per_edge = 2 if launch.weighted else 1
    flops = per_edge * e * d_in
    streams = e * (2 * INDEX_BYTES + (b if launch.weighted else 0))
    gathered = min(e, v) * d_in * b
    if launch.kernel == "fused_transform_reduce":
        flops += 2 * s * d_in * d_out
        return Cost(flops, gathered + streams + d_in * d_out * b
                    + s * d_out * b)
    if launch.kernel == "gather_segment_reduce":
        return Cost(flops, gathered + streams + s * d_out * b)
    raise KeyError(f"no cost function for kernel {launch.kernel!r}")


# ---------------------------------------------------------------------------
# whole-model arithmetic
# ---------------------------------------------------------------------------

def _layer_dims(cfg: dict) -> list:
    dims = ([cfg["num_features"]] + [cfg["hidden_channels"]] * (cfg["num_layers"] - 1)
            + [cfg["num_classes"]])
    return list(zip(dims[:-1], dims[1:]))


def _weights_per_layer(model: str) -> int:
    return {"gcn": 1, "sage": 2}[model]


def _aggregate_flops(model: str, nodes: int, edges: int, width: int) -> int:
    if model == "gcn":
        return 2 * edges * width                 # weighted sum
    return edges * width + nodes * width         # sum, then divide (mean)


def forward_flops(cfg: dict, nodes: int, edges: int) -> float:
    """One forward pass over a graph of ``nodes`` and ``edges``."""
    k = _weights_per_layer(cfg["model"])
    total = 0
    for d_in, d_out in _layer_dims(cfg):
        total += k * 2 * nodes * d_in * d_out
        total += _aggregate_flops(cfg["model"], nodes, edges,
                                  min(d_in, d_out))
    return float(total)


def train_step_flops(cfg: dict, nodes: int, edges: int) -> float:
    """Forward and backward of one full-graph step. The first layer needs no
    gradient of its input, so neither its input-gradient matmul nor a
    backward aggregation (its aggregate from the forward is reused)."""
    k = _weights_per_layer(cfg["model"])
    total = forward_flops(cfg, nodes, edges)
    for i, (d_in, d_out) in enumerate(_layer_dims(cfg)):
        total += k * 2 * nodes * d_in * d_out               # weight grads
        if i > 0:
            total += k * 2 * nodes * d_in * d_out           # input grads
            total += _aggregate_flops(cfg["model"], nodes, edges,
                                      min(d_in, d_out))
    return float(total)


# ---------------------------------------------------------------------------
# trace-time record of kernel launches
# ---------------------------------------------------------------------------

def _itemsize(a) -> int:
    return int(a.dtype.itemsize)


def _gather_launch(h, gather_idx, seg_idx, num_segments, weight=None,
                   **_) -> Launch:
    d = int(h.shape[-1])
    return Launch("gather_segment_reduce", int(h.shape[0]),
                  int(gather_idx.shape[0]), int(num_segments), d, d,
                  weight is not None, _itemsize(h))


def _fused_launch(h, w, gather_idx, seg_idx, num_segments, weight=None,
                  **_) -> Launch:
    return Launch("fused_transform_reduce", int(h.shape[0]),
                  int(gather_idx.shape[0]), int(num_segments),
                  int(h.shape[-1]), int(w.shape[-1]), weight is not None,
                  _itemsize(h))


def _softmax_launch(x, idx, num_segments, **_) -> Launch:
    heads = int(x.shape[-1]) if x.ndim > 1 else 1
    return Launch("segment_softmax", int(x.shape[0]), int(idx.shape[0]),
                  int(num_segments), heads, heads, False, _itemsize(x))


_RECORDERS = {"gather_segment_reduce": _gather_launch,
              "fused_transform_reduce": _fused_launch,
              "segment_softmax": _softmax_launch}


class LaunchRecorder:
    """Collects :class:`Launch` records of every traced call of the kernel
    wrappers in ``repro.kernels.ops`` while :meth:`installed` is open.
    ``take()`` returns what was recorded since the last ``take()``."""

    def __init__(self):
        self._records: list = []

    @contextlib.contextmanager
    def installed(self):
        from repro.kernels import ops as kops
        saved = {name: getattr(kops, name) for name in _RECORDERS}

        def wrap(name, fn):
            def recorded(*args, **kwargs):
                self._records.append(_RECORDERS[name](*args, **kwargs))
                return fn(*args, **kwargs)
            return recorded

        for name, fn in saved.items():
            setattr(kops, name, wrap(name, fn))
        try:
            yield self
        finally:
            for name, fn in saved.items():
                setattr(kops, name, fn)

    def take(self) -> list:
        out, self._records = self._records, []
        return out


def least_time_by_kernel(launches, peaks: Peaks) -> dict:
    """``{kernel: summed least seconds}`` over ``launches``."""
    out: dict = {}
    for launch in launches:
        out[launch.kernel] = (out.get(launch.kernel, 0.0)
                              + kernel_cost(launch).least_s(peaks))
    return out
