"""The on-chip benchmark's harness on the CPU: BENCHMARK.json against the
contract, every piece found by name, new pieces found as new files, and no
result without a TPU."""
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

