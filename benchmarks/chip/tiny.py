"""A cell cut to a size the CPU runs in seconds, for the tests: the same
files, configuration widths and code path, with the graph made small.
Kernels run in the Pallas interpreter there, so nothing of it is a chip
number."""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import types
from pathlib import Path

from benchmarks.chip import harness


def copy_tree(dest: Path) -> Path:
    """BENCHMARK.json and the benchmark's files under ``dest``; returns the
    copy of this directory."""
    here = dest / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    return here


def cell(name: str, dest: Path, *, nodes: int = 256,
         edges: int = 2048) -> harness.Cell:
    """``name`` read from a copy under ``dest`` whose configurations hold a
    ``nodes`` × ``edges`` graph."""
    here = copy_tree(dest)
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        path = dest / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(num_nodes=nodes, num_edges=edges)
        path.write_text(json.dumps(cfg))
    return harness.find_cell(bench, name, root=dest, here=here)


def args(name: str, seed: int, seconds: float, trace: int = 0):
    return types.SimpleNamespace(workload=name, seed=seed, seconds=seconds,
                                 trace=trace)


_RUN_CONFIG = ("jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes",
               "jax_default_matmul_precision")


@contextlib.contextmanager
def isolated_jax_config(cache_dir: Path):
    """Keep a run inside a test from changing the process's JAX settings:
    the compile cache stays off (a directory is named through the
    environment, which JAX reads only at import), and the settings a run
    makes are put back."""
    import jax
    saved = {name: getattr(jax.config, name) for name in _RUN_CONFIG}
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    try:
        yield
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        if old is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = old
