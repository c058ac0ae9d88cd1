"""chip_smoke.py's phases at a tiny size on the CPU, and its refusal to
run without a TPU.

The phases are the chip run's own functions, given tiny models and the
XLA twin of the popcount kernel (``implementation="xla"``); ``main()``
must refuse the CPU backend before any phase runs."""

import importlib.util
import os

import numpy as np
import pytest

from repro.core import TMConfig
from repro.core.compress import encode
from repro.data.pipeline import TM_DATASETS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_model(seed: int):
    cfg = TMConfig(n_classes=3, n_clauses=8, n_features=16)
    rng = np.random.default_rng(seed)
    return cfg, encode(cfg, rng.random((3, 8, 32)) < 0.1)


def test_serve_phase_tiny(smoke):
    (cfg, a), (_, b) = _tiny_model(0), _tiny_model(1)
    out = smoke.serve_phase(cfg, (a, b), implementation="xla", batch_words=1)
    assert out == {"engine": "popcount", "implementation": "xla",
                   "compile_cache_size": 1}


def test_serve_phase_rejects_wrong_engine(smoke):
    """Auto-selection on the CPU gives the XLA twin, which the chip run's
    requirement (the Pallas kernel) must refuse."""
    (cfg, a), (_, b) = _tiny_model(0), _tiny_model(1)
    with pytest.raises(smoke.SmokeFailure, match="implementation"):
        smoke.serve_phase(cfg, (a, b), batch_words=1)


def test_recal_phase_tiny(smoke):
    out = smoke.recal_phase(TM_DATASETS["emg"], n_clauses=10, steps=2)
    assert out == {"train_engine": "packed", "steps": 2}


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_mesh_phase_tiny_on_four_cpu_devices():
    """The ``--chips 4`` phase on four virtual CPU devices (a subprocess:
    the device count is fixed when JAX starts)."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import importlib.util, os, numpy as np
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.core import TMConfig
        from repro.core.compress import encode
        from repro.data.pipeline import TM_DATASETS
        cfg = TMConfig(n_classes=4, n_clauses=8, n_features=16)
        acts = np.random.default_rng(0).random((4, 8, 32)) < 0.1
        out = smoke.mesh_phase(cfg, encode(cfg, acts), TM_DATASETS["emg"],
                               n_clauses=6, implementation="xla")
        print("MESH_OK", out["engine"])
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", f"REPO = {REPO!r}\n" + code],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MESH_OK sharded" in out.stdout
