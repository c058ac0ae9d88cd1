"""Whole benchmark runs at a tiny size on the CPU: the result line's
schema, ``correct`` true on sound runs and false under the control and
under each fault planted in the timed path, and the refusal to run
without a TPU or without the program."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CPU = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=str(ROOT / "src"))
CPU.pop("JAX_COMPILATION_CACHE_DIR", None)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    out = subprocess.run(
        [sys.executable, str(HERE / "drive_cpu.py"),
         str(tmp_path_factory.mktemp("bench_cache"))],
        capture_output=True, text=True, env=CPU, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    return {ln["case"]: ln["result"] for ln in lines}


SOUND = ["closed", "closed_traced", "open", "open_traced"]
BROKEN = ["control", "fault_altered", "fault_half_left_out", "fault_stale"]


@pytest.mark.parametrize("case", SOUND + BROKEN)
def test_result_line_schema(runs, case):
    r = runs[case]
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert isinstance(r["correct"], bool)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        r["device"])
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in r["checks"].values():
        assert set(c) in ({"value", "max"}, {"value", "min"})
    assert r["compiles_in_window"] == 0


@pytest.mark.parametrize("case", SOUND)
def test_sound_runs_are_correct_and_report_their_metrics(runs, case):
    r = runs[case]
    assert r["correct"] is True
    assert r["checks"]["rows_wrong"]["value"] == 0
    assert r["checks"]["rows_checked"]["value"] > 0
    traced = case.endswith("_traced")
    want = {
        "closed": {"rows_per_s", "setup_s"},
        "open": {"latency_p95_ms", "setup_s"},
        "closed_traced": {"batch_fill_pct", "engine_ms_per_batch"},
        "open_traced": {"gen_lag_p95_ms", "queue_wait_p95_ms"},
    }[case]
    assert want <= set(r["metrics"])  # device metrics need the chip
    assert ("breakdown" in r) == traced
    if traced:
        assert {"busy_s", "window_s"} <= set(r["device"])


@pytest.mark.parametrize("case", BROKEN)
def test_control_and_faults_come_out_not_correct(runs, case):
    r = runs[case]
    assert r["correct"] is False
    assert r["checks"]["rows_wrong"]["value"] > 0


@pytest.mark.parametrize("script", ["run_cell.py", "control.py", "sweep.py"])
def test_refuses_to_run_without_a_tpu(script):
    args = {"run_cell.py": ["--seed", "1", "--seconds", "1"],
            "control.py": ["--seeds", "1"],
            "sweep.py": ["--rates", "10"]}[script]
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / script), "--workload",
         "mnist_mixed_open", *args],
        capture_output=True, text=True, env=CPU, timeout=300, cwd=ROOT)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_to_run_with_only_the_benchmark(tmp_path):
    """A checkout of ``BENCHMARK.json`` and its paths alone holds no
    program to measure."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    env = dict(CPU, PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload",
         "mnist_upload32_closed", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300, cwd=tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
