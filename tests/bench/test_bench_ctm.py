"""The Convolutional TM's configuration (``tm_ctm_mnist``): its model
module (``ctm_models``) and reference (``ctm_reference``) against the
program, its work counts, its control, and the ``kernel_ms_per_batch``
reader."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.configs import ctm_models, ctm_reference
from bench.configs.models import load_config
from drive_ctm_cpu import CONFIG as TINY

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CPU = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
CPU.pop("JAX_COMPILATION_CACHE_DIR", None)
SEED = 2**31 + 29
CELL = "ctm_mnist_upload32_closed"


def _fires(truth, x):
    """bool[B, M*C]: the clauses that fire on each image, by brute force
    over positions (``ctm_reference.patch_layout``'s geometry)."""
    pixel, coord = ctm_reference.patch_layout(truth)
    feats = np.concatenate(
        [x[:, pixel], np.broadcast_to(coord, (x.shape[0], *coord.shape))],
        axis=2)  # [B, P, F]
    lits = np.stack([feats, 1 - feats], axis=-1).reshape(*feats.shape[:2], -1)
    acts = truth.actions.reshape(-1, truth.actions.shape[-1])
    ok = np.all(lits[:, :, None, :] >= acts[None, None], axis=-1)  # [B,P,Q]
    return ok.any(axis=1) & acts.any(axis=1)


def test_the_cell_and_its_configuration_are_declared():
    bench = harness.load_benchmark()
    cell = harness.workload(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tm_ctm_mnist", "upload32_closed", 1)
    config = load_config("tm_ctm_mnist")
    assert harness.load_model(config) is ctm_models
    assert harness.load_reference(config["reference"]) is ctm_reference
    assert (config["n_classes"], config["n_clauses"], config["n_includes"],
            config["include_rule"], config["reduced"]) == (
        10, 2000, 200000, "feature", [])
    names = [m["name"] for m in harness.metrics_for(bench, CELL, True)]
    assert "kernel_ms_per_batch" in names and "popcount_roofline" in names


def test_the_model_module_is_seed_stable():
    a = ctm_models.build(TINY, SEED)
    b = ctm_models.build(TINY, SEED)
    c = ctm_models.build(TINY, SEED + 1)
    assert np.array_equal(a.truth.actions, b.truth.actions)
    assert not np.array_equal(a.truth.actions, c.truth.actions)
    assert int(a.truth.actions.sum()) == TINY["n_includes"]
    assert np.array_equal(a.program_model.instructions,
                          b.program_model.instructions)
    rows = [m.rows(64, 4, np.random.default_rng([SEED, 2])) for m in (a, b)]
    assert np.array_equal(*rows) and rows[0].shape == (64, 64)
    assert (a.ops(10), a.bytes(10, 2)) == (b.ops(10), b.bytes(10, 2))


@pytest.mark.parametrize(
    "opts", [{"implementation": "xla"},
             {"implementation": "pallas", "interpret": True}],
    ids=["xla", "pallas-interpret"])
def test_the_program_model_is_the_truth_under_its_geometry(opts):
    """The program's own oracle of the encoded model equals the reference
    on the truth, and so do the class sums served through ``submit``."""
    from repro.accel import Accelerator
    from repro.core import TMConfig, batch_class_sums, state_from_actions

    model = ctm_models.build(TINY, SEED)
    x = model.rows(100, 2, np.random.default_rng(4))
    geom = model.program_model.patch
    assert (geom.n_positions, geom.n_features, geom.input_width) == (
        TINY["n_positions"], TINY["n_features"], TINY["input_width"])
    cfg = TMConfig(TINY["n_classes"], TINY["n_clauses"], geom.n_features,
                   patch=geom)
    want = ctm_reference.class_sums(model.truth, x, 32)
    oracle = batch_class_sums(cfg, state_from_actions(cfg, model.truth.actions),
                              x)
    assert np.array_equal(np.asarray(oracle), want) and want.any()
    acc = Accelerator.for_models([model.program_model], engine_options=opts)
    acc.load("ctm", acc.compile(model.program_model).to_bytes())
    handles = [acc.submit("ctm", x[lo:lo + 64]) for lo in range(0, 100, 64)]
    acc.flush()
    for h in handles:
        h.wait(30)
    assert np.array_equal(np.concatenate([h.class_sums for h in handles]),
                          want)
    assert np.array_equal(ctm_reference.predictions(want),
                          np.argmax(want, axis=1))


def test_planted_rows_make_clauses_fire():
    config = dict(TINY, n_includes=TINY["n_clauses"] * TINY["n_classes"] * 12)
    truth = ctm_models.truth_of(config, SEED)
    keep = ctm_models.plantable(truth)[0]
    assert keep.size > 0
    plain = ctm_models.make_images(truth, 400, 0, np.random.default_rng(1))
    planted = ctm_models.make_images(truth, 400, 4, np.random.default_rng(1))
    n_plain = _fires(truth, plain).sum(axis=1)
    n_planted = _fires(truth, planted).sum(axis=1)
    assert (n_planted >= 1).mean() > 0.95
    assert n_planted.mean() > n_plain.mean() + 2
    # one clause a row: the clause drawn (the generator's next draw after
    # the random bits) fires on its row
    rng = np.random.default_rng(2)
    one = ctm_models.make_images(truth, 300, 1, rng)
    rng = np.random.default_rng(2)
    rng.integers(0, 2, one.shape, dtype=np.uint8)
    drawn = keep[rng.integers(0, keep.size, (300, 1))[:, 0]]
    assert _fires(truth, one)[np.arange(300), drawn].all()


def test_the_reference_folds_positions_in_chunks():
    """Chunks of (row, position) pairs give the sums of one whole product,
    whatever the block of rows."""
    model = ctm_models.build(TINY, SEED)
    x = model.rows(70, 3, np.random.default_rng(5))
    sums = [ctm_reference.class_sums(model.truth, x, b) for b in (1, 7, 128)]
    for s in sums[1:]:
        assert np.array_equal(s, sums[0])
    fires = _fires(model.truth, x).reshape(70, *model.truth.actions.shape[:2])
    vote = np.where(np.arange(TINY["n_clauses"]) % 2 == 0, 1, -1)
    assert np.array_equal(sums[0], (fires * vote).sum(axis=-1))


def test_ops_and_bytes_follow_the_formulas():
    config = load_config("tm_ctm_mnist")
    model = ctm_models.build(config, SEED)
    assert model.program_model.n_instructions == 200000
    rows, batches = 1000, 9
    assert model.ops(rows) == (rows * 361 * (200000 + 20000) + rows * 20000)
    assert model.bytes(rows, batches) == (batches * 2 * 200000
                                          + rows * (784 / 8 + 10 * 4))
    assert model.kernels == {"popcount": "%tm_popcount.1 = "}


def test_the_popcount_pattern_matches_only_the_kernel_events():
    """In a recorded v5e trace the kernel's event is named after its HLO
    instruction, and the ops that read its output name it too: the
    pattern takes the first and not the others."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(HERE / "data" / "trace_spans.xplane.pb"))
    names = [ev.name for p in data.planes for ln in p.lines
             if ln.name == "XLA Ops" for ev in ln.events]
    mention = [n for n in names if "%tm_popcount." in n]
    hits = [n for n in mention if ctm_models.POPCOUNT in n]
    assert hits and all(n.startswith("%tm_popcount.1 = ") for n in hits)
    assert len(hits) < len(mention)  # consumers that name it are left out


def test_kernel_ms_per_batch_reads_a_reduced_trace():
    read = harness.load_reader("kernel_ms_per_batch")
    model = ctm_models.build(TINY, SEED)

    def run(trace, batches=40):
        return harness.Run(config=TINY, model=model, setup_s=1.0,
                           window_s=1.0, records=None,
                           counters={"batches": batches, "rows": 5120,
                                     "padded_rows": 5120, "engine_s": 0.3},
                           peak=None, trace=trace)

    kernel_s = {ctm_models.POPCOUNT: 0.2, "other": 5.0}
    assert read(run({"kernel_s": kernel_s})) == pytest.approx(5.0)
    assert read(run(None)) is None
    assert read(run({"kernel_s": {"other": 1.0}})) is None
    assert read(run({"kernel_s": kernel_s}, batches=0)) is None


@pytest.fixture(scope="module")
def drive(tmp_path_factory):
    out = subprocess.run(
        [sys.executable, str(HERE / "drive_ctm_cpu.py"),
         str(tmp_path_factory.mktemp("ctm_cache"))],
        env=CPU, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return {line["case"]: line for line in map(
        json.loads, out.stdout.strip().splitlines())}


def test_a_cpu_drive_of_the_cell_is_correct(drive):
    result = drive["ctm"]["result"]
    assert result["correct"] and result["checks"]["rows_checked"]["value"] > 0
    assert result["checks"]["rows_wrong"]["value"] == 0


def test_the_control_turns_the_drive_incorrect(drive):
    result = drive["ctm_control"]["result"]
    assert not result["correct"]
    assert result["checks"]["rows_wrong"]["value"] > 0
