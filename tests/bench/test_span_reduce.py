"""The reduction of the serving path's own spans (``bench/span_reduce.py``)
and the metrics that read it: the name list against the program's, the
interval arithmetic of the idle split, a traced CPU run through the
harness, and a recorded chip trace (``data/trace_spans.xplane.pb``: about
50 ms of the EMG cell on one TPU v5e, with the spans)."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, span_reduce
from bench.configs import models
from bench.trace_reduce import reduce_trace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
OLD_TRACE = HERE / "data" / "trace_small.xplane.pb"  # recorded before spans
TRACE = HERE / "data" / "trace_spans.xplane.pb"
PROGRAM_SPAN = {"form_ms_per_batch", "demux_ms_per_batch", "h2d_ms_per_batch",
                "d2h_ms_per_batch"}
DEVICE = {"launch_lag_ms", "readback_lag_ms", "idle_waiting_pct.tput",
          "idle_waiting_pct.lat"}


def test_phase_names_are_the_programs():
    from repro.accel import spans

    assert span_reduce.PHASES == spans.ALL
    assert span_reduce.STEPS == (spans.FORM, spans.H2D, spans.LAUNCH,
                                 spans.D2H, spans.DEMUX)
    assert (span_reduce.BATCH, span_reduce.WAIT) == (spans.BATCH, spans.WAIT)


def test_interval_arithmetic_by_hand():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (29, 40)]
    assert span_reduce._subtract(a, b) == [(0, 2), (4, 8), (22, 29)]
    assert span_reduce._subtract(a, []) == a
    assert span_reduce._subtract([(5, 6)], [(0, 10)]) == []
    assert span_reduce._overlap_ns(a, b) == 2 + 2 + 2 + 1
    assert span_reduce._overlap_ns(a, span_reduce._subtract(a, b)) == 13


def test_clock_offset_follows_the_nearby_enqueues():
    """Each run's offset is the largest enqueue - start bound among the
    runs enqueued within ``OFFSET_WINDOW_S`` of it."""
    sec = span_reduce.OFFSET_WINDOW_S * 1e9
    enqueued = [0.0, 0.5 * sec, 1.6 * sec, 3.0 * sec]
    bounds = [10.0, 12.0, 5.0, 20.0]
    runs = [(q - b, q - b + 1.0, q) for q, b in zip(enqueued, bounds)]
    assert span_reduce._offsets(runs) == [12.0, 12.0, 5.0, 20.0]


def test_a_program_without_spans_gives_nothing():
    """A program from before the spans: the metrics then read None and
    are left out of the result line."""
    assert span_reduce.reduce_spans(OLD_TRACE, harness.WINDOW) is None


@pytest.mark.parametrize("metric", sorted(PROGRAM_SPAN | DEVICE))
def test_untraced_runs_read_nothing(metric):
    assert harness.load_reader(metric)(SimpleNamespace(trace=None)) is None


@pytest.fixture(scope="module")
def cpu_runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, str(HERE / "drive_spans_cpu.py"),
         str(tmp_path_factory.mktemp("span_cache"))],
        capture_output=True, text=True, env=env, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    return {ln["case"]: ln for ln in lines}


def test_closed_traced_cpu_run_reports_the_span_metrics(cpu_runs):
    run = cpu_runs["closed_traced"]
    metrics, spans = run["result"]["metrics"], run["spans"]
    assert run["result"]["correct"] is True
    assert PROGRAM_SPAN <= set(metrics)
    assert not DEVICE & set(metrics)  # no device plane on the CPU
    n = spans["batches"]
    assert n > 0 and spans["devices"] == 0 and spans["idle_split_s"] is None
    for name in PROGRAM_SPAN:
        phase = "tm." + name.split("_")[0]
        assert metrics[name]["value"] == pytest.approx(
            1e3 * spans["phases"][phase]["total_s"] / n)


@pytest.mark.parametrize("case", ["closed_traced", "open_traced"])
def test_cpu_span_table_counts_each_phase_once_per_batch(cpu_runs, case):
    spans = cpu_runs[case]["spans"]
    phases, n = spans["phases"], spans["batches"]
    assert n > 0 and phases["tm.batch"]["count"] == n
    for name in span_reduce.STEPS:
        assert phases[name]["count"] == n
    steps = sum(phases[name]["total_s"] for name in span_reduce.STEPS)
    assert steps <= phases["tm.batch"]["total_s"]
    assert phases["tm.batch"]["total_s"] + phases["tm.wait"]["total_s"] \
        <= spans["window_s"]


@pytest.fixture(scope="module")
def chip():
    return (span_reduce.reduce_spans(TRACE, harness.WINDOW),
            reduce_trace(TRACE, harness.WINDOW, [models.POPCOUNT]))


def test_chip_idle_split_sums_to_the_idle_time(chip):
    spans, reduced = chip
    assert spans["devices"] == reduced["devices"] == 1
    split = spans["idle_split_s"]
    assert set(split) == set(span_reduce.LEAVES) | {"batch_other", "loop"}
    assert all(v >= 0 for v in split.values())
    assert sum(split.values()) == pytest.approx(spans["idle_s"], rel=1e-9)
    assert sum(split.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-3)


def test_chip_every_batch_lags_its_device_work(chip):
    spans, _ = chip
    n = spans["batches"]
    for lag in ("launch_lag", "readback_lag"):
        assert spans[lag]["count"] == n
        assert spans[lag]["min_ms"] >= 0


def test_chip_counts_each_phase_once_per_batch(chip):
    spans, _ = chip
    n = spans["batches"]
    assert n > 0
    for name in span_reduce.STEPS:
        assert spans["phases"][name]["count"] == n


def test_chip_device_clock_is_aligned_by_the_enqueue_flows(chip):
    """On the v5e this trace shows each execution about a millisecond
    before the host enqueued it; within 50 ms the offset holds still."""
    offset = chip[0]["clock_offset"]
    assert 0.5 < offset["ms"] < 2.0
    assert offset["drift_ms"] == 0


def test_chip_kernel_and_step_keep_their_names(chip):
    from jax.profiler import ProfileData

    _, reduced = chip
    assert reduced["kernel_s"][models.POPCOUNT] > 0  # popcount_roofline's match
    events = [(ln.name, ev.name) for p in ProfileData.from_file(
        str(TRACE)).planes for ln in p.lines for ev in ln.events]
    kernel = [n for line, n in events
              if line == "XLA Ops" and models.POPCOUNT in n]
    assert kernel and all(n.startswith("%tm_popcount.") for n in kernel)
    modules = {n.split("(")[0] for line, n in events if line == "XLA Modules"}
    assert modules == {"jit_tm_popcount_step"}
