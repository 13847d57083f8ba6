"""chip_smoke.py's phases at a tiny size on the CPU (Pallas interpreter):
the control flow of the chip run, without the chip."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402

TINY = dict(nodes=300, edges=2400, feat=16, classes=5, hidden=32, layers=3,
            lr=0.01, steps=3, seed=0)


@pytest.fixture(scope="module")
def trained():
    return chip_smoke.train_phase(**TINY)


def test_train_phase_tiny(trained):
    chip_smoke.check_training(trained, expect_kernels=False)
    assert trained["launched"] <= set(chip_smoke.KERNELS)
    assert "gather_segment_reduce" in trained["launched"]
    assert len(trained["step_s"]) == TINY["steps"]
    d_abs, d_rel = chip_smoke.forward_parity(trained)
    assert d_rel <= chip_smoke.TOL, (d_abs, d_rel)


def test_serve_phase_tiny(trained):
    sv = chip_smoke.serve_phase(trained["params"], feat=TINY["feat"],
                                classes=TINY["classes"], num_graphs=3,
                                min_nodes=8, max_nodes=40)
    assert sv["served"] == sv["graphs"] == 3
    assert sv["compiles"] == sv["warm_compiles"] == sv["buckets"]
    assert sv["hit_rate"] == 1.0
    assert sv["max_rel"] <= chip_smoke.TOL


def test_check_training_rejects_rising_loss(trained):
    bad = dict(trained, losses=[1.0, 1.5])
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_training(bad, expect_kernels=False)


def test_custom_call_parser():
    text = ('  %gather_segment_reduce.1 = f32[8,128]{1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call"\n'
            '  %fused_transform_reduce = f32[8,128]{1,0} custom-call(%b), '
            'custom_call_target="tpu_custom_call"\n'
            '  %other.3 = f32[8] custom-call(%c), custom_call_target="foo"\n')
    calls = chip_smoke.custom_calls(text)
    assert calls == {"gather_segment_reduce": 1, "fused_transform_reduce": 1}
    assert chip_smoke.launched_kernels(
        {"fused:gather_segment_reduce_weighted": 2,
         "fused:segment_reduce_sum": 1, "unfused:x": 1}) == {
        "gather_segment_reduce", "segment_reduce"}


def test_entry_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out
    assert "needs a TPU" in out.err


def test_diff_is_relative_to_reference_scale():
    d_abs, d_rel = chip_smoke.diff(np.array([1.0, 2.0]), np.array([1.0, 4.0]))
    assert (d_abs, d_rel) == (2.0, 0.5)
