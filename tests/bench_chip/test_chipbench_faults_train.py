"""A training cell's comparison catches a broken timed path: a run on the
CPU at a tiny size, through the harness past its look for a chip, comes
out correct as the program stands and not correct with each fault the cell
can have planted underneath; the lower-precision control fails too."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import compare, graphs, harness, tiny  # noqa: E402
from benchmarks.chip.drivers.train import CHECKED_STEPS  # noqa: E402

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


@pytest.fixture(autouse=True)
def _own_jax_config(tmp_path):
    with tiny.isolated_jax_config(tmp_path / "jax_cache"):
        yield


def _run(name, tmp_path, capsys, seed=2**33 + 17):
    cell = tiny.cell(name, tmp_path)
    assert harness.execute(cell, tiny.args(name, seed, 0.2), 0.0) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, tmp_path, capsys):
    out = _run(name, tmp_path, capsys)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_step_s", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
def test_a_step_that_returns_its_state_unchanged_fails(name, tmp_path, capsys,
                                                        monkeypatch):
    from repro.train.trainer import Trainer
    executable = Trainer.executable

    def stuck(self, static):
        exe = executable(self, static)
        return lambda state, arrays: (state, exe(state, arrays)[1])

    monkeypatch.setattr(Trainer, "executable", stuck)
    out = _run(name, tmp_path, capsys)
    assert out["correct"] is False
    assert out["checks"]["update_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", CELLS)
def test_half_the_batch_left_out_fails(name, tmp_path, capsys, monkeypatch):
    import jax.numpy as jnp
    from repro.train.task import NodeClassification
    loss = NodeClassification.loss

    def half(self, params, arrays, static, rng, *, mesh=None):
        n = static.num_nodes
        mask = (jnp.arange(n) < n // 2).astype(jnp.float32)
        return loss(self, params, dict(arrays, label_mask=mask), static, rng,
                    mesh=mesh)

    monkeypatch.setattr(NodeClassification, "loss", half)
    out = _run(name, tmp_path, capsys)
    assert out["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", CELLS)
def test_the_lower_precision_control_fails(name, seed):
    # the cell's reference with every matmul in three bf16 passes
    # (``high``, the precision below the configuration's ``highest``), in
    # the program's place, against the float32 reference: the control
    # whose chip readings set the limits
    cell = harness.find_cell(harness.load_benchmark(), name)
    cfg = dict(cell.config, num_nodes=2048, num_edges=14104)
    g = graphs.make_graph(cell.traffic["law"], cfg["num_nodes"],
                          cfg["num_edges"], cfg["num_features"],
                          cfg["num_classes"], graphs.rng_for(seed, "graph"))
    ref = cell.model.train(cfg, g, seed, steps=CHECKED_STEPS)
    control = cell.model.train(cfg, g, seed, steps=CHECKED_STEPS,
                               precision="high")
    ok, checks = compare.judge(compare.train_numbers(control, ref),
                               cell.limits)
    assert not ok, checks
