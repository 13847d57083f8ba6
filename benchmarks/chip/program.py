"""Where the benchmark hands its inputs to the program and reads back what
the program made: the weights as the program's parameter tree, a graph as
the program's ``Graph``, and the program's trees as plain arrays."""
from __future__ import annotations

import jax
import numpy as np


def to_params(params: list, cfg: dict):
    """The benchmark's weights in the program's parameter tree (a list of
    ``{name: P}``), shaped as ``repro.models.gnn.init`` shapes it with the
    configuration's ``model_options``."""
    from repro.models import gnn
    from repro.models.params import P
    shapes = jax.eval_shape(
        lambda: gnn.init(jax.random.PRNGKey(0), cfg["model"],
                         cfg["num_features"], cfg["hidden_channels"],
                         cfg["num_classes"], cfg["num_layers"],
                         **cfg.get("model_options", {})))
    out = []
    for mine, theirs in zip(params, shapes, strict=True):
        if set(mine) != set(theirs):
            raise ValueError(f"parameter names differ: {sorted(mine)} vs "
                             f"{sorted(theirs)}")
        for k, p in theirs.items():
            if tuple(p.value.shape) != tuple(mine[k].shape):
                raise ValueError(f"{k}: shape {mine[k].shape} vs "
                                 f"{p.value.shape}")
        out.append({k: P(mine[k], p.axes) for k, p in theirs.items()})
    return out


def from_tree(tree) -> list:
    """The program's list of ``{name: P}`` as ``{name: np.ndarray}``."""
    return [{k: np.asarray(p.value) for k, p in layer.items()}
            for layer in tree]


def to_graph(g, name: str):
    """A benchmark graph as the program's ``repro.data.graphs.Graph``."""
    from repro.data.graphs import Graph
    return Graph(name=name, edge_index=np.stack([g.src, g.dst]),
                 num_nodes=g.num_nodes, x=g.x, labels=g.labels,
                 deg_inv_sqrt=g.deg_inv_sqrt)


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))
