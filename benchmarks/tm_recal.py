"""Recalibration-pipeline benchmark: the Fig-8 loop under the clock.

Measures the costs that bound how fast a deployment can chase drift:

  * trainer throughput  — ``fit_step``s/sec (and samples/sec) of the
    incremental training node, per TrainEngine plugin ('reference' host
    path vs the fused packed-TA 'packed' kernel vs the 'sharded'
    dist-mesh step, all replaying the identical (key, step, batch)
    sequence — the column doubles as a bit-identity check);
  * swap-to-first-correct-prediction latency — wall time from calling
    ``register`` (drain-then-swap) on a live slot to a served, correct
    prediction under the NEW model;
  * accuracy-vs-drift curve — stale-model accuracy vs post-recal accuracy
    at each drift level, recalibrated through the full controller path
    (buffer -> fine-tune -> validated compress -> hot-swap -> post-swap
    validation).

Emits ``BENCH_tm_recal.json`` (CWD) + harness CSV rows.

    PYTHONPATH=src python -m benchmarks.run --only tm_recal

``BENCH_TINY=1`` shrinks everything for the CI smoke step.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import TMConfig
from repro.data.pipeline import TMDatasetSpec, booleanized_tm_dataset
from repro.dist.sharding import make_mesh
from repro.recal import (
    DriftMonitor,
    RecalController,
    RecalWorker,
    make_train_engine,
)
from repro.serve_tm import ServeCapacity, TMServer

OUT_PATH = "BENCH_tm_recal.json"


def _tiny() -> bool:
    return os.environ.get("BENCH_TINY", "0") == "1"


def _bench_trainer(worker, x, y, batch: int, steps: int) -> dict:
    """Steady-state fit_step throughput (first call compiles, excluded)."""
    xb, yb = x[:batch], y[:batch]
    worker.fine_tune(xb, yb)  # warm the jit cache
    t0 = time.perf_counter()
    for _ in range(steps):
        worker.fine_tune(xb, yb)
    jax.block_until_ready(worker.state)
    dt = time.perf_counter() - t0
    return {
        "steps_timed": steps,
        "steps_per_s": steps / dt,
        "samples_per_s": steps * batch / dt,
        "us_per_step": dt / steps * 1e6,
    }


def _bench_train_engines(cfg, state0, x, y, batch: int, steps: int) -> dict:
    """Per-TrainEngine steady-state fit_step throughput on identical work.

    Every engine replays the SAME (key, step, batch) sequence from the
    same initial state — the throughput column therefore doubles as a
    bit-identity audit: each engine's final canonical state must equal
    the reference's (``bit_identical``), or the speed number is
    meaningless.  The sharded engine runs on a 1x1 mesh here (the
    single-process bench box); its column measures shard_map overhead at
    trivial scale, not scaling."""
    xb = jnp.asarray(np.asarray(x[:batch], np.uint8))
    yb = jnp.asarray(np.asarray(y[:batch], np.int32))
    key = jax.random.key(0x7E57)
    mesh = make_mesh((1, 1), ("data", "model"))
    engines = {
        "reference": make_train_engine("reference", cfg),
        "packed": make_train_engine("packed", cfg),
        "sharded": make_train_engine("sharded", cfg, mesh=mesh, batch=batch),
    }
    out, finals = {}, {}
    for name, eng in engines.items():
        internal = eng.prepare(state0)
        internal = eng.fit_step(internal, key, xb, yb, step=0)  # warm jit
        jax.block_until_ready(internal)
        t0 = time.perf_counter()
        for s in range(1, steps + 1):
            internal = eng.fit_step(internal, key, xb, yb, step=s)
        jax.block_until_ready(internal)
        dt = time.perf_counter() - t0
        finals[name] = np.asarray(eng.canonical(internal))
        out[name] = {
            "steps_timed": steps,
            "steps_per_s": steps / dt,
            "samples_per_s": steps * batch / dt,
            "us_per_step": dt / steps * 1e6,
        }
    for name, stats in out.items():
        stats["bit_identical"] = bool(
            np.array_equal(finals[name], finals["reference"])
        )
        stats["speedup_vs_reference"] = (
            stats["steps_per_s"] / out["reference"]["steps_per_s"]
        )
    return out


def _swap_to_first_correct(server, slot, model, probe_x, probe_y) -> float:
    """Seconds from initiating the hot-swap to a served correct prediction
    under the new program (the paper's runtime-reprogram turnaround)."""
    t0 = time.perf_counter()
    server.register(slot, model, provenance="bench:swap")
    preds = server.infer(slot, probe_x)
    dt = time.perf_counter() - t0
    if not (preds == probe_y).any():
        raise RuntimeError("probe traffic produced no correct prediction")
    return dt


def run():
    tiny = _tiny()
    spec = (
        TMDatasetSpec("recal-bench", 8, 3, 4, 24) if tiny
        else TMDatasetSpec("recal-bench", 16, 4, 4, 40)
    )
    n_train = 600 if tiny else 2000
    batch = 100 if tiny else 200
    timed_steps = 5 if tiny else 30
    drifts = (0.6, 1.2) if tiny else (0.4, 0.8, 1.2)
    epochs_initial = 3 if tiny else 5
    epochs_recal = 6 if tiny else 10

    xb, y, booler = booleanized_tm_dataset(spec, n_train, seed=0, drift=0.0)
    cfg = TMConfig(
        n_classes=spec.n_classes, n_clauses=spec.n_clauses,
        n_features=booler.n_boolean_features,
    )
    worker = RecalWorker(cfg, key=jax.random.key(7))
    worker.fine_tune_epochs(xb, y, epochs=epochs_initial, batch=batch)

    train_stats = _bench_trainer(worker, xb, y, batch, timed_steps)
    train_stats["engine"] = worker.train_engine
    engine_stats = _bench_train_engines(
        cfg, jnp.asarray(worker.snapshot()), xb, y, batch, timed_steps
    )

    server = TMServer(
        ServeCapacity(feature_capacity=128, instruction_capacity=8192),
        backend="plan",
    )
    controller = RecalController(
        server, "edge", worker,
        monitor=DriftMonitor(min_samples=64),
        buffer_batches=8, train_batch_size=batch,
        epochs_per_recal=epochs_recal,
    )
    controller.deploy()
    # warm the engine + measure the clean baseline
    xt, yt, _ = booleanized_tm_dataset(
        spec, 256, seed=1, drift=0.0, booleanizer=booler
    )
    baseline_acc = float((controller.observe(xt, yt) == yt).mean())
    controller.freeze_baseline()

    # swap latency: reinstall the current model into the LIVE slot with
    # traffic queued, then serve a labelled probe under the new version
    probe_x, probe_y, _ = booleanized_tm_dataset(
        spec, 32, seed=2, drift=0.0, booleanizer=booler
    )
    model_now = controller.compressor.compress(cfg, worker.state).model
    swap_lat = []
    for _ in range(3 if tiny else 8):
        server.submit("edge", probe_x)  # queued traffic the swap must drain
        swap_lat.append(
            _swap_to_first_correct(server, "edge", model_now, probe_x, probe_y)
        )
    swap_s = float(np.median(swap_lat))

    # accuracy-vs-drift: stale accuracy, recalibrate, recovered accuracy
    curve = []
    for drift in drifts:
        for i in range(4):
            xd, yd, _ = booleanized_tm_dataset(
                spec, batch, seed=50 + i + int(drift * 100),
                drift=drift, booleanizer=booler,
            )
            controller.observe(xd, yd)
        xe, ye, _ = booleanized_tm_dataset(
            spec, 512, seed=60 + int(drift * 100), drift=drift,
            booleanizer=booler,
        )
        acc_before = float((controller.observe(xe, ye) == ye).mean())
        event = controller.recalibrate(reason=f"bench:drift={drift}")
        acc_after = float((controller.observe(xe, ye) == ye).mean())
        curve.append({
            "drift": drift,
            "acc_before": acc_before,
            "acc_after": acc_after,
            "rolled_back": event.rolled_back,
            "train_s": event.train_s,
            "compress_s": event.compress_s,
            "swap_s": event.swap_s,
        })

    summary = server.metrics.summary()
    report = {
        "bench": "tm_recal",
        "tiny": tiny,
        "model": {
            "n_classes": cfg.n_classes,
            "n_clauses": cfg.n_clauses,
            "n_features": cfg.n_features,
        },
        "baseline_acc": baseline_acc,
        "train": train_stats,
        "train_engines": engine_stats,
        "swap_to_first_correct_us": swap_s * 1e6,
        "curve": curve,
        "recals": summary["recals"],
        "rollbacks": summary["rollbacks"],
        "swaps": summary["swaps"],
        "throughput_dps": summary["throughput_dps"],
        "compile_cache_size": server.compile_cache_size(),
    }
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=1)

    recovered = ";".join(
        f"d{c['drift']}={c['acc_before']:.2f}->{c['acc_after']:.2f}"
        for c in curve
    )
    return [
        (
            "tm_recal_train",
            f"{train_stats['us_per_step']:.1f}",
            f"steps_per_s={train_stats['steps_per_s']:.1f}"
            f";samples_per_s={train_stats['samples_per_s']:.0f}",
        ),
        (
            "tm_recal_train_engines",
            f"{engine_stats['packed']['speedup_vs_reference']:.2f}",
            ";".join(
                f"{n}={s['steps_per_s']:.1f}steps_per_s"
                f"(bit_identical={s['bit_identical']})"
                for n, s in engine_stats.items()
            ),
        ),
        (
            "tm_recal_swap",
            f"{swap_s * 1e6:.1f}",
            f"swap_to_first_correct;cache={server.compile_cache_size()}",
        ),
        (
            "tm_recal_loop",
            f"{summary['engine_us']['p50']:.1f}",
            recovered,
        ),
    ]
