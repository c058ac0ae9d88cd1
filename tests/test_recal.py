"""Behaviour tests for the repro.recal online-recalibration subsystem
(the closed Fig-8 loop) and its supporting serve_tm/train/dist changes."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    TMConfig,
    fit_step,
    init_state,
    train_batch,
    train_batch_parallel,
)
from repro.core.compress import encode, validate_roundtrip
from repro.data.pipeline import TMDatasetSpec, booleanized_tm_dataset
from repro.dist.sharding import make_mesh
from repro.dist.steps import make_tm_train_step
from repro.recal import (
    Compressor,
    DriftMonitor,
    RecalController,
    RecalWorker,
)
from repro.serve_tm import ServeCapacity, TMServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _random_batch(rng, B, F, M):
    x = rng.integers(0, 2, (B, F)).astype(np.uint8)
    y = rng.integers(0, M, B).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


# ---------------------------------------------------------------------------
# seeding contract (fold-in keys, resumable fit_step)
# ---------------------------------------------------------------------------

def test_train_batch_reproducible_for_same_key():
    cfg = TMConfig(n_classes=3, n_clauses=6, n_features=8)
    rng = np.random.default_rng(0)
    xb, yb = _random_batch(rng, 16, 8, 3)
    key = jax.random.key(9)
    s1 = train_batch(cfg, init_state(cfg, key), key, xb, yb)
    s2 = train_batch(cfg, init_state(cfg, key), key, xb, yb)
    assert jnp.array_equal(s1, s2)


def test_fit_step_is_resumable():
    """Step s yields the same update no matter how many steps ran before —
    the contract the RecalWorker's snapshot/restore relies on."""
    cfg = TMConfig(n_classes=3, n_clauses=6, n_features=8)
    rng = np.random.default_rng(1)
    key = jax.random.key(3)
    b0 = _random_batch(rng, 16, 8, 3)
    b1 = _random_batch(rng, 16, 8, 3)

    # path A: steps 0 then 1
    sA = fit_step(cfg, init_state(cfg, key), key, *b0, step=0, parallel=True)
    sA = fit_step(cfg, sA, key, *b1, step=1, parallel=True)
    # path B: step 1 applied to a checkpoint of step 0's result
    sB = fit_step(cfg, init_state(cfg, key), key, *b0, step=0, parallel=True)
    ckpt = np.asarray(sB)  # host checkpoint (train steps donate buffers)
    sB = fit_step(cfg, jnp.asarray(ckpt), key, *b1, step=1, parallel=True)
    assert jnp.array_equal(sA, sB)


def test_sharded_tm_train_step_matches_parallel_trainer():
    """make_tm_train_step on a 1x1 mesh is bit-identical to
    train_batch_parallel (same fold-in sample keys, same deltas)."""
    cfg = TMConfig(n_classes=4, n_clauses=8, n_features=6)
    rng = np.random.default_rng(2)
    xb, yb = _random_batch(rng, 32, 6, 4)
    key = jax.random.key(5)
    ref = train_batch_parallel(cfg, init_state(cfg, key), key, xb, yb)
    mesh = make_mesh((1, 1), ("data", "model"))
    step = make_tm_train_step(cfg, mesh, batch=32)
    out = step(init_state(cfg, key), key, xb, yb)
    assert jnp.array_equal(ref, out)


@pytest.mark.slow
def test_sharded_tm_train_step_multidevice():
    """Bit-equality on a real (2 data x 2 model) mesh: classes sharded over
    model, batch over data, global sample keys derived per shard."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import TMConfig, init_state, train_batch_parallel
            from repro.dist.sharding import make_mesh
            from repro.dist.steps import make_tm_train_step
            cfg = TMConfig(n_classes=4, n_clauses=8, n_features=6)
            rng = np.random.default_rng(0)
            xb = jnp.asarray(rng.integers(0, 2, (32, 6)).astype(np.uint8))
            yb = jnp.asarray(rng.integers(0, 4, 32).astype(np.int32))
            key = jax.random.key(5)
            ref = train_batch_parallel(
                cfg, init_state(cfg, key), key, xb, yb)
            mesh = make_mesh((2, 2), ("data", "model"))
            step = make_tm_train_step(cfg, mesh, batch=32)
            out = step(init_state(cfg, key), key, xb, yb)
            assert jnp.array_equal(ref, out), "mesh step diverged"
            print("SHARDED_OK")
        """)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARDED_OK" in out.stdout


# ---------------------------------------------------------------------------
# DriftMonitor
# ---------------------------------------------------------------------------

def _sums(margin, n, M=4):
    """Class-sum rows with an exact top1-top2 gap of ``margin``."""
    s = np.zeros((n, M), np.int32)
    s[:, 0] = margin
    return s


def test_monitor_warmup_then_margin_trigger():
    mon = DriftMonitor(window=64, min_samples=32, margin_fraction=0.5)
    preds = np.zeros(16, np.int32)
    mon.observe(_sums(10, 16), preds)
    assert not mon.decision().trigger  # warmup: below min_samples
    mon.observe(_sums(10, 32), np.zeros(32, np.int32))
    mon.freeze_baseline()
    assert mon.decision().reason == "healthy"
    # margin collapses below 0.5 x baseline -> trigger without any labels
    mon.observe(_sums(1, 64), np.zeros(64, np.int32))
    d = mon.decision()
    assert d.trigger and "margin" in d.reason and d.accuracy is None


def test_monitor_accuracy_trigger_beats_margin():
    mon = DriftMonitor(window=64, min_samples=16, accuracy_threshold=0.9)
    preds = np.zeros(32, np.int32)
    labels = np.ones(32, np.int32)  # everything wrong
    mon.observe(_sums(10, 32), preds, labels)
    d = mon.decision()
    assert d.trigger and "accuracy" in d.reason and d.accuracy == 0.0


def test_monitor_reset_clears_windows():
    mon = DriftMonitor(window=64, min_samples=16)
    mon.observe(_sums(10, 32), np.zeros(32, np.int32), np.zeros(32, np.int32))
    mon.reset()
    assert mon.n_samples == 0 and mon.accuracy is None
    assert mon.decision().reason == "warmup"


# ---------------------------------------------------------------------------
# Compressor / publication gate
# ---------------------------------------------------------------------------

def test_compressor_emits_validated_model():
    cfg = TMConfig(n_classes=3, n_clauses=6, n_features=10)
    rng = np.random.default_rng(3)
    key = jax.random.key(1)
    state = train_batch_parallel(
        cfg, init_state(cfg, key), key, *_random_batch(rng, 64, 10, 3)
    )
    report = Compressor(probe_rows=32).compress(cfg, state)
    assert report.model.n_classes == 3
    assert report.probe_rows == 32
    assert report.n_includes == int(
        np.asarray(state > cfg.n_states).sum()
    )


def test_compressor_rejects_bad_traffic_sample_shape():
    cfg = TMConfig(n_classes=2, n_clauses=4, n_features=6)
    state = init_state(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="traffic_sample"):
        Compressor().compress(
            cfg, state, traffic_sample=np.zeros((4, 5), np.uint8)
        )


def test_validate_roundtrip_catches_tampered_stream():
    """A corrupted instruction stream must never pass the publication gate."""
    cfg = TMConfig(n_classes=2, n_clauses=2, n_features=4)
    acts = np.zeros((2, 2, 8), bool)
    acts[0, 0, 0] = True  # class 0, + clause, literal f0
    acts[1, 0, 2] = True  # class 1, + clause, literal f1
    model = encode(cfg, acts)
    X = np.eye(4, dtype=np.uint8)
    validate_roundtrip(cfg, acts, model, X)  # intact stream passes
    tampered = np.array(model.instructions)
    tampered[0] += 1  # corrupt the offset: include lands on the wrong slot
    import dataclasses
    bad = dataclasses.replace(model, instructions=tampered)
    with pytest.raises(ValueError, match="not bit-exact"):
        validate_roundtrip(cfg, acts, bad, X)


# ---------------------------------------------------------------------------
# registry / server rollback hooks
# ---------------------------------------------------------------------------

def _tiny_model(seed, M=3, C=4, F=8, density=0.2):
    rng = np.random.default_rng(seed)
    cfg = TMConfig(n_classes=M, n_clauses=C, n_features=F)
    acts = rng.random((M, C, 2 * F)) < density
    return cfg, acts, encode(cfg, acts)


def test_registry_rollback_and_provenance():
    server = TMServer(ServeCapacity(), backend="plan")
    _, _, m1 = _tiny_model(1)
    _, _, m2 = _tiny_model(2)
    server.register("s", m1, provenance="deploy")
    server.register("s", m2, provenance="recal:test")
    assert server.registry.get("s").version == 2
    assert server.registry.get("s").provenance == "recal:test"
    assert server.registry.previous("s").model is m1

    entry = server.rollback("s")
    assert entry.version == 3  # versions stay monotonic
    assert entry.model is m1
    # provenance nests the restored entry's own provenance (full chain)
    assert entry.provenance == "rollback:v2->v1(deploy)"
    assert server.metrics.rollbacks == 1
    assert server.metrics.summary()["rollbacks"] == 1


def test_registry_rollback_without_history_raises():
    server = TMServer(ServeCapacity(), backend="plan")
    _, _, m1 = _tiny_model(1)
    server.register("s", m1)
    with pytest.raises(KeyError, match="no previous version"):
        server.rollback("s")


def test_server_rollback_drains_queued_traffic_under_current_model():
    """Rows queued before a rollback are answered by the model they were
    submitted against (same drain discipline as register)."""
    server = TMServer(ServeCapacity(), backend="plan")
    cfg1, acts1, m1 = _tiny_model(4)
    cfg2, acts2, m2 = _tiny_model(5)
    server.register("s", m1)
    server.register("s", m2)

    rng = np.random.default_rng(6)
    x = rng.integers(0, 2, (8, cfg2.n_features)).astype(np.uint8)
    expected_v2 = np.asarray(server.class_sums("s", x)).argmax(1)
    h = server.submit("s", x)
    server.rollback("s")  # must flush the queue under m2 first
    assert np.array_equal(h.result(), expected_v2)
    assert server.compile_cache_size() == 1


# ---------------------------------------------------------------------------
# controller: the closed loop
# ---------------------------------------------------------------------------

SPEC = TMDatasetSpec("recal-test", 12, 3, 4, 24)


def _trained_setup(backend="plan"):
    xb, y, booler = booleanized_tm_dataset(SPEC, 900, seed=0, drift=0.0)
    cfg = TMConfig(
        n_classes=SPEC.n_classes, n_clauses=SPEC.n_clauses,
        n_features=booler.n_boolean_features,
    )
    worker = RecalWorker(cfg, key=jax.random.key(11))
    worker.fine_tune_epochs(xb, y, epochs=4, batch=150)
    server = TMServer(
        ServeCapacity(feature_capacity=64, instruction_capacity=8192),
        backend=backend,
    )
    return cfg, worker, server, booler


def test_controller_closes_the_loop_under_drift():
    cfg, worker, server, booler = _trained_setup()
    controller = RecalController(
        server, "edge", worker,
        monitor=DriftMonitor(window=256, min_samples=128,
                             accuracy_threshold=0.9),
        buffer_batches=6, train_batch_size=128, min_buffer_rows=512,
        epochs_per_recal=6,
    )
    controller.deploy()
    assert server.registry.get("edge").provenance == "deploy"

    xt, yt, _ = booleanized_tm_dataset(
        SPEC, 256, seed=1, drift=0.0, booleanizer=booler
    )
    base_acc = float((controller.observe(xt, yt) == yt).mean())
    controller.freeze_baseline()
    assert base_acc > 0.8

    events = []
    for i in range(14):
        xd, yd, _ = booleanized_tm_dataset(
            SPEC, 128, seed=100 + i, drift=1.2, booleanizer=booler
        )
        _, event = controller.serve(xd, yd)
        if event:
            events.append(event)
    assert events, "drift never triggered a recalibration"
    assert any(not e.rolled_back for e in events)
    swap = next(e for e in events if not e.rolled_back)
    assert swap.holdout_acc_after >= swap.holdout_acc_before
    assert server.registry.get("edge").provenance.startswith(
        ("recal:", "rollback:")
    )

    xf, yf, _ = booleanized_tm_dataset(
        SPEC, 512, seed=999, drift=1.2, booleanizer=booler
    )
    final_acc = float((controller.observe(xf, yf) == yf).mean())
    # the tight recovery bound (baseline - 2%) is the example's acceptance
    # criterion at full scale; this miniature loop just has to get close
    assert final_acc >= base_acc - 0.08
    assert server.compile_cache_size() == 1
    assert server.metrics.summary()["recals"] == len(events)


def test_controller_rolls_back_a_bad_recalibration():
    cfg, worker, server, booler = _trained_setup()

    class SabotagedWorker(RecalWorker):
        """Training node gone wrong: unlearns everything."""

        def fine_tune_epochs(self, x, y, *, epochs, batch):
            self.state = init_state(self.cfg, self.key)  # all-Exclude
            return 1

    bad = SabotagedWorker(cfg, state=jnp.asarray(worker.snapshot()),
                          key=jax.random.key(11))
    controller = RecalController(
        server, "edge", bad, buffer_batches=4, train_batch_size=128,
        regression_margin=0.02,
    )
    controller.deploy()
    good_state = bad.snapshot()
    xt, yt, _ = booleanized_tm_dataset(
        SPEC, 256, seed=1, drift=0.0, booleanizer=booler
    )
    expected = controller.observe(xt, yt)

    event = controller.recalibrate(reason="test")
    assert event.rolled_back
    assert server.metrics.rollbacks == 1
    # the served model is the pre-recal one again, the worker restored
    assert np.array_equal(controller.server.infer("edge", xt), expected)
    assert np.array_equal(bad.snapshot(), good_state)
    assert server.compile_cache_size() == 1


def test_controller_requires_labelled_buffer():
    cfg, worker, server, _ = _trained_setup()
    controller = RecalController(server, "edge", worker)
    controller.deploy()
    with pytest.raises(RuntimeError, match="no labelled traffic"):
        controller.recalibrate()
