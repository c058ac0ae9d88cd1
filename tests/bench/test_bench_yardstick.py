"""The work count, the peaks, the plain reference and the trace
reduction, each against numbers worked out by hand or from a recorded
trace (``data/trace_small.xplane.pb``: 50 ms of the EMG cell on one
TPU v5e)."""

from pathlib import Path

import numpy as np
import pytest

from bench import harness, work
from bench.configs import models, tm_reference
from bench.trace_reduce import reduce_trace

TRACE = Path(__file__).resolve().parent / "data" / "trace_small.xplane.pb"


def test_work_on_a_hand_counted_model():
    # 2 classes x 3 clauses, 5 includes, 4 features, 7 rows in 2 batches
    assert models.serve_ops(7, n_includes=5, n_clauses=6) == 7 * 11
    # per batch 2 x 5 bytes of stream; per row 8 literal bits = 1 byte in
    # and 2 x 4 bytes of sums out
    assert models.serve_bytes(7, 2, n_includes=5, n_features=4,
                              n_classes=2) == 20 + 7 * 9


def test_roofline_names_its_bound():
    pk = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline(200, 10, 4.0, pk) == (50.0, "ops")
    assert work.roofline(10, 30, 6.0, pk) == (50.0, "bytes")


def test_peaks_know_the_v5e_and_refuse_an_unknown_chip():
    pk = work.peak("TPU v5 lite")
    assert pk["int8_ops_per_s"] == 393e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peak("TPU v9 imaginary")


def test_reference_on_a_hand_built_model():
    acts = np.zeros((2, 2, 6), bool)  # 3 features, interleaved literals
    acts[0, 0, [0, 3]] = True  # x0 and not x1  (votes +1 for class 0)
    acts[0, 1, [4]] = True  # x2              (votes -1 for class 0)
    acts[1, 0, [1]] = True  # not x0          (votes +1 for class 1)
    x = np.array([[1, 0, 1], [0, 0, 0], [1, 1, 0]], np.uint8)
    want = np.array([[0, 0], [0, 1], [0, 0]], np.int32)
    got = tm_reference.class_sums(acts, x, block_rows=2)
    assert np.array_equal(got, want)
    assert np.array_equal(tm_reference.predictions(got), [0, 1, 0])


def test_reference_agrees_with_the_programs_dense_oracle():
    from repro.core import TMConfig, batch_class_sums, state_from_actions

    rng = np.random.default_rng(0)
    acts = rng.random((4, 10, 40)) < 0.08
    x = rng.integers(0, 2, (50, 20), dtype=np.uint8)
    cfg = TMConfig(n_classes=4, n_clauses=10, n_features=20)
    want = np.asarray(batch_class_sums(cfg, state_from_actions(cfg, acts), x))
    assert np.array_equal(tm_reference.class_sums(acts, x, 16), want)


def test_control_drops_exactly_the_last_include_of_each_clause():
    acts = np.zeros((1, 3, 6), bool)
    acts[0, 0, [1, 4]] = True
    acts[0, 2, [5]] = True
    out = tm_reference.drop_last_include(acts)
    assert out[0, 0].tolist() == [0, 1, 0, 0, 0, 0]
    assert not out[0, 1].any() and not out[0, 2].any()
    assert acts.sum() == 3  # the input is left alone


@pytest.fixture(scope="module")
def reduced():
    return reduce_trace(TRACE, harness.WINDOW, [models.POPCOUNT])


def _events():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(TRACE))
    planes = {p.name: p for p in data.planes}
    ops = [ev for ln in planes["/device:TPU:0"].lines
           if ln.name == "XLA Ops" for ev in ln.events]
    window = [ev for p in data.planes if p.name.startswith("/host:")
              for ln in p.lines for ev in ln.events
              if ev.name == harness.WINDOW]
    return ops, window[0]


def test_trace_window_devices_and_kernel(reduced):
    ops, window = _events()
    assert reduced["devices"] == 1  # the Megascale plane is no device
    assert reduced["window_s"] == pytest.approx(window.duration_ns / 1e9)
    inside = [ev for ev in ops
              if ev.start_ns >= window.start_ns and ev.end_ns <= window.end_ns]
    # ops on one core run one at a time: busy is their summed time
    assert reduced["busy_s"] == pytest.approx(
        sum(ev.duration_ns for ev in inside) / 1e9, rel=1e-6)
    kernel = [ev for ev in inside if models.POPCOUNT in ev.name]
    assert len(kernel) > 0
    assert reduced["kernel_s"][models.POPCOUNT] == pytest.approx(
        sum(ev.duration_ns for ev in kernel) / 1e9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_trace_idle_gaps_cover_the_idle_time(reduced):
    gaps = reduced["idle_gaps"]
    assert 0 < len(gaps) <= 10 and len(reduced["device_ops"]) <= 10
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-3)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # the host was blocked reading results back for most of the idle time
    assert gaps[0][0] == "np.asarray(jax.Array)"
