"""The model seam: a configuration names the module that builds its model
(``bench/configs/<model>.py``), and the harness, the generators and the
work-count readers know the model only through the ``BenchModel`` it
builds.  ``seam_model`` is more than (classes, clauses, features): a
``TMConfig`` keyword beyond the three, a truth whose literal width is not
the row width, and a work count and kernel pattern of its own."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import seam_model
import seam_reference
from bench import harness, work
from bench.configs import models
from bench.traffic.common import Records
from drive_seam_cpu import CONFIG, KERNEL_S, PEAK

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CPU = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
CPU.pop("JAX_COMPILATION_CACHE_DIR", None)


@pytest.fixture
def installed(monkeypatch):
    """``seam_model`` and its reference, found by name as the harness
    finds every module of ``bench/configs``."""
    monkeypatch.setitem(sys.modules, "bench.configs.seam_model", seam_model)
    monkeypatch.setitem(sys.modules, "bench.configs.seam_reference",
                        seam_reference)


def test_a_configuration_names_its_model_module(installed):
    from repro.core import TMConfig, batch_class_sums, state_from_actions

    assert harness.load_model(CONFIG) is seam_model
    assert harness.load_model({k: v for k, v in CONFIG.items()
                               if k != "model"}) is models
    model = harness.load_model(CONFIG).build(CONFIG, 2**31 + 5)
    f = CONFIG["n_features"]
    assert model.truth.shape == (3, 8, 2 * f)
    assert model.program_model.n_features == f + seam_model.PAD
    x = model.rows(40, 2, np.random.default_rng(3))
    assert x.shape == (40, f + seam_model.PAD) and x.dtype == np.uint8
    # the program's own oracle over the wide model agrees with the
    # reference over the truth's features
    cfg = TMConfig(n_classes=3, n_clauses=8, n_features=f + seam_model.PAD,
                   threshold=CONFIG["threshold"])
    wide = np.pad(model.truth, ((0, 0), (0, 0), (0, 2 * seam_model.PAD)))
    want = np.asarray(batch_class_sums(cfg, state_from_actions(cfg, wide), x))
    got = harness.load_reference(CONFIG["reference"]).class_sums(
        model.truth, x)
    assert np.array_equal(got, want) and want.any()


def test_an_unknown_model_fails_with_the_known_modules():
    with pytest.raises(KeyError, match=r"no_such_model.*known: .*'models'"):
        harness.load_model(dict(CONFIG, model="no_such_model"))


def _run(model, kernel_s):
    n = 4
    records = Records(
        pool=np.zeros(n, np.int32), index=np.arange(n),
        due=np.full(n, np.nan), sent=np.linspace(0.1, 0.4, n),
        queue_delay=np.zeros(n), done=np.array([0.2, 0.5, 0.7, 1.4]),
        failed=np.zeros(n, bool), sums=[None] * n, preds=[None] * n,
        rows=np.array([8, 8, 1, 8]), t_start=0.0, t_end=1.0)
    return harness.Run(
        config=CONFIG, model=model, setup_s=1.0, window_s=1.0,
        records=records, counters={"batches": 5, "rows": 300,
                                   "padded_rows": 640, "engine_s": 0.1},
        peak=PEAK, trace={"kernel_s": kernel_s})


def test_work_readers_count_the_models_own_work(installed):
    seam = seam_model.build(CONFIG, 1)
    plain = models.build(CONFIG, 1)
    roofline = harness.load_reader("popcount_roofline")
    mfu = harness.load_reader("serve_mfu_pct")
    run = _run(seam, {seam_model.KERNEL: KERNEL_S})
    want = work.roofline(seam_model.serve_ops(CONFIG, 300),
                         seam_model.serve_bytes(CONFIG, 300, 5), KERNEL_S,
                         PEAK)[0]
    assert roofline(run) == want
    assert mfu(run) == 100.0 * 17 * seam_model.serve_ops(CONFIG, 1) / 1e9
    # the default model counts other work, under the popcount pattern
    other = _run(plain, {models.POPCOUNT: KERNEL_S})
    assert roofline(other) not in (None, want) and mfu(other) != mfu(run)
    # each reads its own kernel's pattern and nothing else
    assert roofline(_run(seam, {models.POPCOUNT: KERNEL_S})) is None


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    out = subprocess.run(
        [sys.executable, str(HERE / "drive_seam_cpu.py"),
         str(tmp_path_factory.mktemp("seam_cache"))],
        capture_output=True, text=True, env=CPU, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    return {ln["case"]: ln for ln in lines}


def test_seam_model_runs_correct_through_run_cell(runs):
    run = runs["seam"]
    r = run["result"]
    assert r["correct"] is True and r["failed"] == 0
    assert r["checks"]["rows_checked"]["value"] > 0
    assert r["compiles_in_window"] == 0
    c = run["counters"]
    assert run["readers"]["popcount_roofline"] == work.roofline(
        seam_model.serve_ops(CONFIG, c["rows"]),
        seam_model.serve_bytes(CONFIG, c["rows"], c["batches"]), KERNEL_S,
        PEAK)[0]
    assert run["readers"]["serve_mfu_pct"] == pytest.approx(
        100.0 * run["rows_done"] / run["window_s"]
        * seam_model.serve_ops(CONFIG, 1) / PEAK["int8_ops_per_s"],
        rel=1e-12)


def test_seam_control_uses_the_configurations_reference(runs):
    r = runs["seam_control"]["result"]
    assert r["correct"] is False and r["failed"] == 0
    assert r["checks"]["rows_wrong"]["value"] > 0
