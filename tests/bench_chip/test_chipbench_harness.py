"""The on-chip benchmark's harness on the CPU: BENCHMARK.json against the
contract, every piece found by name, new pieces (a model among them) found
as new files, and no result without a TPU."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, tiny  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in BENCH["workloads"]:
        assert NAME.match(c["name"]) and c["chips"] in (1, 4)
        assert c["name"] == f"{c['config']}.{c['traffic']}"
        assert len(c["why"]) <= 200
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_pieces(cell):
    c = harness.find_cell(BENCH, cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_metric(m["name"]))
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]):
            assert m["source"] == "device_trace"
    assert harness.load_driver(c.traffic).run
    assert c.limits and all(v > 0 for v in c.limits.values())
    cfg_file = {x["name"]: x for x in BENCH["configs"]}[
        cell.split(".")[0]]["file"]
    assert cfg_file.startswith(tuple(BENCH["paths"]))


def test_a_new_cell_config_traffic_and_metric_are_new_files(tmp_path):
    here = tiny.copy_tree(tmp_path)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    (here / "configs" / "gcn-wide.json").write_text(json.dumps(
        dict(json.loads((here / "configs" / "gcn-arxiv.json").read_text()),
             hidden_channels=512)))
    (here / "traffic" / "train-denser.json").write_text(json.dumps(
        {"driver": "train", "law": {"kind": "citation", "exponent": 2.5}}))
    (here / "limits" / "gcn-wide.train-denser.json").write_text(json.dumps(
        {"loss_gap": 1e-5, "grad_gap": 1e-4, "update_gap": 1e-3}))
    (here / "metrics" / "edges_per_step.train.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "gcn-wide", "source": "x",
                             "file": "benchmarks/chip/configs/gcn-wide.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "gcn-wide.train-denser",
                               "config": "gcn-wide",
                               "traffic": "train-denser", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "edges_per_step.train", "unit": "1",
                               "better": "higher",
                               "source": "program_counter", "layer": "x",
                               "moves": "train_step_s",
                               "workloads": ["gcn-wide.train-denser"]})
    bench["end_to_end"][0]["workloads"].append("gcn-wide.train-denser")
    cell = harness.find_cell(bench, "gcn-wide.train-denser", root=tmp_path,
                             here=here)
    assert cell.config["hidden_channels"] == 512
    assert cell.traffic["law"] == {"kind": "citation", "exponent": 2.5}
    assert [m["name"] for m in cell.per_layer] == ["edges_per_step.train"]
    assert harness.load_metric("edges_per_step.train", here)({}) == 42.0
    with pytest.raises(harness.CellError):
        harness.find_cell(bench, "gcn-wide.nothing", root=tmp_path,
                          here=here)


# a model the benchmark did not have: GCN through the shared reference,
# under a name of its own, noting each call
RECORDED_MODEL = '''"""GCN through the shared reference, noting each call."""
from benchmarks.chip.costs import train_step_flops as _flops
from benchmarks.chip.reference import init_params as _init, train as _train

CALLS = []


def init_params(cfg, seed):
    CALLS.append("init_params")
    return _init(cfg, seed)


def train(cfg, graph, seed, *, steps, precision="highest", rows=None):
    CALLS.append(("train", precision))
    return _train(cfg, graph, seed, steps=steps, precision=precision,
                  rows=rows)


def train_step_flops(cfg, nodes, edges):
    CALLS.append("train_step_flops")
    return _flops(cfg, nodes, edges)
'''
NEW_CELL = "gcn-recorded.train-recorded"


def _with_new_model(dest: Path, reference: str = "gcn_recorded") -> dict:
    """A copy under ``dest`` with a new model, configuration, traffic mix,
    limits and entries, as a PR that adds a model writes them; returns its
    BENCHMARK.json."""
    here = tiny.copy_tree(dest)
    (here / "references" / "gcn_recorded.py").write_text(RECORDED_MODEL)
    cfg = json.loads((here / "configs" / "gcn-arxiv.json").read_text())
    (here / "configs" / "gcn-recorded.json").write_text(json.dumps(
        dict(cfg, reference=reference, num_nodes=256, num_edges=2048)))
    (here / "traffic" / "train-recorded.json").write_text(json.dumps(
        {"driver": "train", "law": {"kind": "citation", "exponent": 3.0}}))
    (here / "limits" / f"{NEW_CELL}.json").write_text(
        (here / "limits" / "gcn-arxiv.train-powerlaw.json").read_text())
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "gcn-recorded", "source": "x",
         "file": "benchmarks/chip/configs/gcn-recorded.json", "reduced": [],
         "why": "x"})
    bench["workloads"].append({"name": NEW_CELL, "config": "gcn-recorded",
                               "traffic": "train-recorded", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(NEW_CELL)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def _new_cell(dest: Path) -> harness.Cell:
    bench = _with_new_model(dest)
    return harness.find_cell(bench, NEW_CELL, root=dest,
                             here=dest / "benchmarks" / "chip")


def test_a_new_model_is_found_by_name(tmp_path):
    here = tmp_path / "benchmarks" / "chip"
    cell = _new_cell(tmp_path)
    assert Path(cell.model.__file__) == here / "references" / "gcn_recorded.py"
    assert harness.load_model("gcn_recorded", here).CALLS == []
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in BENCH["per_layer"]}
    with pytest.raises(harness.CellError, match="gcn_recorded"):
        harness.load_model("gcn_recorded")       # not in the tree itself


@pytest.mark.parametrize("name", ["gcn", "sage"])
def test_gcn_and_sage_are_served_by_the_shared_reference(name):
    from benchmarks.chip import costs, reference
    model = harness.load_model(name)
    assert model.init_params is reference.init_params
    assert model.train is reference.train
    assert model.train_step_flops is costs.train_step_flops


def test_an_unknown_model_is_a_cell_error(tmp_path):
    bench = _with_new_model(tmp_path, reference="gat_nowhere")
    path = tmp_path / "benchmarks" / "chip" / "references" / "gat_nowhere.py"
    with pytest.raises(harness.CellError, match=re.escape(str(path))):
        harness.find_cell(bench, NEW_CELL, root=tmp_path,
                          here=tmp_path / "benchmarks" / "chip")
    cfg = json.loads((tmp_path / "benchmarks" / "chip" / "configs"
                      / "gcn-recorded.json").read_text())
    del cfg["reference"]
    (tmp_path / "benchmarks" / "chip" / "configs"
     / "gcn-recorded.json").write_text(json.dumps(cfg))
    with pytest.raises(harness.CellError, match="names no reference"):
        harness.find_cell(bench, NEW_CELL, root=tmp_path,
                          here=tmp_path / "benchmarks" / "chip")


@pytest.fixture
def _own_jax_config(tmp_path):
    with tiny.isolated_jax_config(tmp_path / "jax_cache"):
        yield


def _execute(cell, capsys) -> dict:
    args = tiny.args(cell.name, 2**33 + 29, 0.2)
    assert harness.execute(cell, args, 0.0) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.usefixtures("_own_jax_config")
def test_a_new_model_runs_correct_through_the_driver(tmp_path, capsys):
    cell = _new_cell(tmp_path)
    out = _execute(cell, capsys)
    assert out["correct"] is True, out["checks"]
    assert sorted(map(str, cell.model.CALLS)) == [
        "('train', 'highest')", "init_params", "train_step_flops"]


@pytest.mark.usefixtures("_own_jax_config")
def test_a_new_model_catches_half_the_batch_and_the_control(
        tmp_path, capsys, monkeypatch):
    import jax.numpy as jnp
    from benchmarks.chip import compare, graphs
    from benchmarks.chip.drivers.train import CHECKED_STEPS
    from repro.train.task import NodeClassification
    cell = _new_cell(tmp_path)
    loss = NodeClassification.loss

    def half(self, params, arrays, static, rng, *, mesh=None):
        n = static.num_nodes
        mask = (jnp.arange(n) < n // 2).astype(jnp.float32)
        return loss(self, params, dict(arrays, label_mask=mask), static, rng,
                    mesh=mesh)

    with monkeypatch.context() as m:
        m.setattr(NodeClassification, "loss", half)
        assert _execute(cell, capsys)["correct"] is False

    cfg = dict(cell.config, num_nodes=2048, num_edges=14104)
    g = graphs.make_graph(cell.traffic["law"], cfg["num_nodes"],
                          cfg["num_edges"], cfg["num_features"],
                          cfg["num_classes"], graphs.rng_for(1, "graph"))
    ref = cell.model.train(cfg, g, 1, steps=CHECKED_STEPS)
    control = cell.model.train(cfg, g, 1, steps=CHECKED_STEPS,
                               precision="high")
    ok, checks = compare.judge(compare.train_numbers(control, ref),
                               cell.limits)
    assert not ok, checks
    assert ("train", "high") in cell.model.CALLS


def _run_py(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "gcn-arxiv.train-powerlaw", "--seed", "3", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].lstrip().startswith("{")


def test_no_tpu_means_a_nonzero_exit_and_no_result():
    proc = _run_py(ROOT)
    assert proc.returncode != 0 and _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_the_benchmark_alone_gives_no_result(tmp_path):
    tiny.copy_tree(tmp_path)            # BENCHMARK.json and paths only
    proc = _run_py(tmp_path)
    assert proc.returncode != 0 and _no_result(proc)

