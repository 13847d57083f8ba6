"""The benchmark's own graph generators.

They are copies of laws, not calls into the program: a later change to the
program's generators leaves the yardstick as it is. Everything is drawn from
``seed`` and a named stream, so one seed gives one set of inputs.

* citation: in-degrees of a citation network, a power law in the number of
  citations received (see :func:`citation_dst`), sources uniform. Every
  seed gets the same multiset of in-degrees, in another order over the
  nodes, so that every seed asks the same work of the kernels.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one use of one seed; any whole number is a seed."""
    s = int(seed) % 2**64
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32,
                                  zlib.crc32(stream.encode())])


@dataclasses.dataclass(frozen=True)
class GraphData:
    """A destination-sorted graph with node features and labels."""
    src: np.ndarray          # (E,) int32
    dst: np.ndarray          # (E,) int32, non-decreasing
    x: np.ndarray            # (V, F) float32
    labels: np.ndarray       # (V,) int32
    num_nodes: int

    @property
    def num_edges(self) -> int:
        return int(self.dst.shape[0])

    @property
    def deg_inv_sqrt(self) -> np.ndarray:
        """D^-1/2 of the in-degree, 1 for isolated nodes."""
        deg = np.bincount(self.dst, minlength=self.num_nodes)
        return (1.0 / np.sqrt(np.maximum(deg, 1))).astype(np.float32)


def citation_dst(nodes: int, edges: int, rng: np.random.Generator, *,
                 exponent: float) -> np.ndarray:
    """Destinations whose in-degrees follow P(k) ~ k^-exponent.

    By rank, such a law gives the node of rank r an in-degree proportional
    to r^(-1/(exponent-1)). The degrees are that share of ``edges``, rounded
    by largest remainder so that they sum to ``edges`` exactly, and dealt
    to the nodes in an order the seed picks."""
    rank = np.arange(1, nodes + 1, dtype=np.float64)
    share = rank ** (-1.0 / (exponent - 1.0))
    share *= edges / share.sum()
    deg = np.floor(share).astype(np.int64)
    deg[np.argsort(deg - share, kind="stable")[:edges - int(deg.sum())]] += 1
    return np.repeat(np.arange(nodes, dtype=np.int32), rng.permutation(deg))


def make_graph(law: dict, nodes: int, edges: int, feat: int, classes: int,
               rng: np.random.Generator) -> GraphData:
    """One graph under ``law`` (``{"kind": "citation", "exponent"}``)."""
    if law["kind"] != "citation":
        raise ValueError(f"unknown degree law {law['kind']!r}")
    dst = citation_dst(nodes, edges, rng, exponent=float(law["exponent"]))
    src = rng.integers(0, nodes, size=edges, dtype=np.int32)
    x = rng.standard_normal((nodes, feat), dtype=np.float32)
    labels = rng.integers(0, classes, size=nodes, dtype=np.int32)
    return GraphData(src, dst, x, labels, nodes)

