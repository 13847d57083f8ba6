"""The on-chip benchmark's yardstick, checked on the CPU: trace reduction,
operations and bytes, the launch recorder, the peaks table, the readers
and the generators."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import costs, graphs, peaks, readers, trace  # noqa: E402

# a hand-built trace: one TPU with three ops (two overlapping) and the
# host's python line with the window annotation and one program span
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 3000000 }
  }
  lines { id: 2 name: "Steps" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%gather_segment_reduce.3 = f32[8,128] custom-call(s32[8] %a)" } }
  event_metadata { key: 2 value { id: 2
    name: "%fused_transform_reduce = f32[8,256] custom-call(s32[8] %a)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.12 = f32[8] fusion()" } }
  event_metadata { key: 4 value { id: 4 name: "0" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
}
"""


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return trace.read_data(ProfileData.from_text_proto(XSPACE))


def test_trace_reads_device_ops_and_host_annotations(recorded):
    devices, host = recorded
    assert [d.device for d in devices] == [0]
    assert len(devices[0].events) == 4          # the XLA Ops line only
    assert host["bench.window"] == [(0.0, 10000.0)]
    assert host["bench.step"] == [(4500.0, 5500.0)]


def test_busy_union_and_idle_share(recorded):
    devices, host = recorded
    lo, hi = host["bench.window"][0]
    s = trace.summarize(devices, lo, hi, chips=1)
    # ops cover [1000, 4000) ∪ [6000, 7000) ∪ [9000, 10000) of [0, 10000)
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(5e-6)
    assert s.idle_share == pytest.approx(0.5)
    assert s.gaps == [(0.0, 1000.0), (4000.0, 6000.0), (7000.0, 9000.0)]


def test_device_time_by_stable_name(recorded):
    devices, host = recorded
    s = trace.summarize(devices, *host["bench.window"][0], chips=1)
    # the last gather event is clipped to the window: 2 µs + 1 µs
    assert s.op_s == pytest.approx({"gather_segment_reduce": 3e-6,
                                    "fused_transform_reduce": 2e-6,
                                    "fusion": 1e-6})
    assert trace.top_ops(s.op_s, top=1) == [["gather_segment_reduce",
                                             pytest.approx(3e-6)]]


def test_idle_gaps_are_named_by_the_deepest_covering_span(recorded):
    devices, host = recorded
    s = trace.summarize(devices, *host["bench.window"][0], chips=1)
    spans = [("train.step", 3900.0, 9500.0), ("train.prepare", 4000.0, 6000.0)]
    got = dict((k, v) for k, v in trace.label_gaps(s.gaps, spans))
    assert got == pytest.approx({"host:outside_spans": 1e-6,
                                 "train.prepare": 2e-6, "train.step": 2e-6})


def test_summarize_refuses_missing_chips(recorded):
    devices, host = recorded
    with pytest.raises(ValueError):
        trace.summarize(devices, 0.0, 1.0, chips=4)


def test_stable_names():
    assert trace.stable_name("%gather_segment_reduce.4 = f32[8] x()") == \
        "gather_segment_reduce"
    assert trace.stable_name("fusion.12") == "fusion"
    assert trace.stable_name("%copy-start = (f32[2]) copy-start()") == \
        "copy-start"


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

def test_gather_segment_reduce_cost_by_hand():
    # 10 rows of width 4, 6 weighted edges into 3 segments, fp32
    launch = costs.Launch("gather_segment_reduce", num_rows=10, num_edges=6,
                          num_segments=3, d_in=4, d_out=4, weighted=True,
                          io_bytes=4)
    c = costs.kernel_cost(launch)
    assert c.flops == 2 * 6 * 4                      # one mul + add / value
    # 6 gathered rows · 4 · 4 B + 6 · (4 + 4 + 4) B of streams + 3 · 4 · 4 B
    assert c.bytes == 96 + 72 + 48


def test_fused_transform_reduce_cost_by_hand():
    # unweighted: 5 edges gathered from 3 rows (each read once) of width 2,
    # into 2 segments, transformed by a 2 x 3 weight
    launch = costs.Launch("fused_transform_reduce", num_rows=3, num_edges=5,
                          num_segments=2, d_in=2, d_out=3, weighted=False,
                          io_bytes=4)
    c = costs.kernel_cost(launch)
    assert c.flops == 5 * 2 + 2 * 2 * 2 * 3
    # rows 3·2·4 + streams 5·8 + weight 2·3·4 + out 2·3·4
    assert c.bytes == 24 + 40 + 24 + 24
    v5e = peaks.peaks_for("TPU v5 lite")
    # memory-bound: 112 B at 819 GB/s outlasts 34 flops at 197 TFLOP/s
    assert c.least_s(v5e) == pytest.approx(112 / 819e9)


def test_segment_softmax_cost_by_hand():
    # 6 logits of 3 heads into 2 segments, fp32: per element a max, a
    # subtraction, an exponential, a sum and a division
    launch = costs.Launch("segment_softmax", num_rows=6, num_edges=6,
                          num_segments=2, d_in=3, d_out=3, weighted=False,
                          io_bytes=4)
    c = costs.kernel_cost(launch)
    assert c.flops == 5 * 6 * 3
    # logits 6·3·4 read + ids 6·4 read + weights 6·3·4 written
    assert c.bytes == 72 + 24 + 72


def test_the_recorder_notes_a_softmax_launch():
    import jax
    import jax.numpy as jnp
    from repro.core import ops
    logits = jnp.arange(12, dtype=jnp.float32).reshape(6, 2) / 7
    idx = jnp.array([0, 0, 1, 1, 1, 3], jnp.int32)
    recorder = costs.LaunchRecorder()
    with recorder.installed():
        for x in (logits, logits[:, 0]):
            jax.jit(lambda x: ops.segment_softmax(x, idx, 4,
                                                  impl="pallas"))(x)
    assert recorder.take() == [
        costs.Launch("segment_softmax", 6, 6, 4, 2, 2, False, 4),
        costs.Launch("segment_softmax", 6, 6, 4, 1, 1, False, 4)]


def test_cost_of_an_unknown_kernel_is_an_error():
    with pytest.raises(KeyError):
        costs.kernel_cost(costs.Launch("sddmm", 1, 1, 1, 1, 1, False, 4))


def test_train_step_flops_of_gcn_arxiv():
    cfg = {"model": "gcn", "num_features": 128, "hidden_channels": 256,
           "num_layers": 3, "num_classes": 40}
    v, e = 169343, 1166243
    gemm = 2 * v * (128 * 256 + 256 * 256 + 256 * 40)
    agg = 2 * e * (128 + 256 + 40)
    fwd = gemm + agg
    bwd = gemm + 2 * v * (256 * 256 + 256 * 40) + 2 * e * (256 + 40)
    assert costs.forward_flops(cfg, v, e) == fwd
    assert costs.train_step_flops(cfg, v, e) == fwd + bwd
    assert 95e9 < fwd + bwd < 115e9


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite").flops_per_s == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


def test_readers_find_nothing_without_their_inputs():
    assert readers.span_self_ms({"spans": [], "span_units": 3},
                                ("train.sample",)) is None
    assert readers.mfu({"flops": 0.0, "window_s": 1.0}) is None


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------

LAW = {"kind": "citation", "exponent": 3.0}


@pytest.mark.parametrize("law", [LAW, dict(LAW, exponent=2.5)])
def test_graphs_are_deterministic_in_the_seed(law):
    seed = 2**33 + 5
    a = graphs.make_graph(law, 300, 2000, 8, 5, graphs.rng_for(seed, "g"))
    b = graphs.make_graph(law, 300, 2000, 8, 5, graphs.rng_for(seed, "g"))
    c = graphs.make_graph(law, 300, 2000, 8, 5, graphs.rng_for(seed + 1, "g"))
    for f in ("src", "dst", "x", "labels"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.src, c.src)
    assert np.all(np.diff(a.dst) >= 0)


@pytest.mark.parametrize("law", [LAW, dict(LAW, exponent=2.5)])
def test_every_seed_gets_the_same_in_degrees(law):
    v, e = 2000, 13775
    a, b = (np.bincount(graphs.make_graph(law, v, e, 4, 3,
                                          graphs.rng_for(s, "g")).dst,
                        minlength=v) for s in (1, 2**33 + 1))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), np.sort(b))


def test_citation_law_at_arxiv_size():
    # P(k) ~ k^-3 by rank: in-degree ∝ r^-1/2, so the most cited node gets
    # E / Σ r^-1/2 ≈ 1,166,243 / 821.6 ≈ 1419.5 and the least about
    # 1419.5 / √169,343 ≈ 3.45; every node is cited
    v, e = 169343, 1166243
    g = graphs.make_graph(LAW, v, e, 1, 2, graphs.rng_for(7, "graph"))
    deg = np.bincount(g.dst, minlength=v)
    assert deg.sum() == e and np.all(np.diff(g.dst) >= 0)
    assert deg.max() == 1420 and deg.min() == 3
    rank = np.sort(deg)[::-1]
    # the rank law: the 100th most cited node has a tenth of the first's
    assert rank[99] == pytest.approx(rank[0] / 10, rel=0.01)
