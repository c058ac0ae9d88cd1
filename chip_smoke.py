"""Chip smoke run: the TM serving and recalibration path on a TPU.

    python chip_smoke.py             # one chip: device check, serve, recal
    python chip_smoke.py --chips 4   # four chips: the mesh phase only

Drives the main path through the entry points a deployment calls, at the
paper's largest configuration (MNIST: 10 classes x 200 clauses x 784
features, ~17k includes; ``benchmarks/tm_bench_common.py``), with models
and data made from seeds:

* **serve** — ``Accelerator.for_models`` negotiates the envelope and must
  auto-select the ``popcount`` engine with its Pallas kernel; the model is
  compiled to a ``TMProgram``, shipped as bytes, loaded, and served by the
  running scheduler to requests of 1, 32 and 128 rows in two priority
  lanes.  Every answer must equal the dense oracle
  (``core.tm.batch_class_sums``) and the XLA twin of the kernel bit for
  bit.  A second model is then hot-swapped in while traffic is queued;
  queued requests keep the old model, later ones get the new one, and the
  engine must not have recompiled.
* **recal** — a few ``RecalWorker`` steps on the ``mnist`` dataset spec
  with the auto-selected ``packed`` train engine, bit-identical to the
  ``reference`` engine.
* **mesh** (``--chips 4`` only) — the ``sharded`` serving and train
  engines on a 2x2 (data, model) mesh, bit-exact against the oracle, the
  one-chip ``popcount`` engine and the ``packed`` train engine.

Everything runs in this one process, which holds the chip(s).  Without a
TPU the run exits non-zero before any phase.  Times printed are set-up
(compile + first call), never speed.  The last line of standard output is
``{"ok": true, "device": {...}}``, and only when every check passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.tm_bench_common import synthetic_mnist_scale  # noqa: E402
from repro.accel import Accelerator, make_engine  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import TMConfig, batch_class_sums, state_from_actions  # noqa: E402
from repro.core.compress import decode  # noqa: E402
from repro.data.pipeline import TM_DATASETS, booleanized_tm_dataset  # noqa: E402
from repro.dist.sharding import make_mesh  # noqa: E402
from repro.recal import RecalWorker  # noqa: E402

SLOT = "tenant"
LANES = ("critical", "normal")
ROWS = (1, 32, 128)
WAIT_S = 600.0  # per handle; the first batch includes the engine compile
TRAIN_BATCH = 32  # rows per recal step: what the reference engine can hold
SEED = 0  # requests, training data and TA init


class SmokeFailure(AssertionError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


_oracle = jax.jit(batch_class_sums, static_argnums=0)


def oracle_sums(cfg: TMConfig, model, x: np.ndarray) -> np.ndarray:
    """Dense-oracle class sums int32[B, M] for a compressed model."""
    state = state_from_actions(cfg, decode(model))
    return np.asarray(_oracle(cfg, state, jnp.asarray(x)))


def _requests(rng, n_features: int, rows=ROWS):
    return [
        (rng.integers(0, 2, (n, n_features), dtype=np.uint8), lane)
        for n in rows
        for lane in LANES
    ]


def _check_served(twin, twin_prog, cfg, model, sent, label: str) -> int:
    """Wait on every handle; its class sums and predictions must equal
    the oracle's and the twin engine's.  Returns the rows checked."""
    rows = 0
    for x, handle in sent:
        preds = handle.wait(WAIT_S)  # raises the handle's failure, if any
        want = oracle_sums(cfg, model, x)
        got = handle.class_sums
        require(got is not None, f"{label}: handle {handle.rid} has no sums")
        require(np.array_equal(got, want),
                f"{label}: {x.shape[0]}-row request != dense oracle")
        cap = twin.plan.batch_capacity
        twin_sums = np.concatenate([
            twin.class_sums(twin_prog, x[i:i + cap])
            for i in range(0, x.shape[0], cap)
        ])
        require(np.array_equal(twin_sums, want),
                f"{label}: {x.shape[0]}-row request: {twin.name}/"
                f"{twin.implementation} != oracle")
        require(np.array_equal(preds, np.argmax(want, axis=1)),
                f"{label}: predictions != oracle argmax")
        rows += x.shape[0]
    return rows


def serve_phase(cfg: TMConfig, models, *, implementation=None,
                batch_words: int = 4) -> dict:
    """Negotiate, compile, ship, load, serve, hot-swap, stop.

    ``implementation=None`` leaves the engine to auto-selection, which on
    a TPU must be ``popcount`` with its Pallas kernel; a test passes
    ``"xla"`` to run the same phase on the CPU."""
    model_a, model_b = models
    options = None if implementation is None else {
        "implementation": implementation
    }
    acc = Accelerator.for_models(
        [model_a, model_b], batch_words=batch_words, engine_options=options
    )
    engine = acc.engine
    want_impl = implementation or "pallas"
    say(f"serve: engine={engine.name} implementation="
        f"{engine.implementation} plan={acc.plan.as_dict()}")
    require(engine.name == "popcount",
            f"auto-selected engine is {engine.name!r}, not 'popcount'")
    require(engine.implementation == want_impl,
            f"popcount implementation is {engine.implementation!r}, "
            f"not {want_impl!r}")

    blob_a = acc.compile(model_a).to_bytes()
    blob_b = acc.compile(model_b).to_bytes()
    acc.load(SLOT, blob_a)
    twin = make_engine("popcount", acc.plan, implementation="xla")
    twin_a, twin_b = twin.program(model_a), twin.program(model_b)
    rng = np.random.default_rng(SEED)

    acc.start()
    try:
        t0 = time.perf_counter()
        first = acc.submit(SLOT, rng.integers(0, 2, (1, cfg.n_features),
                                              dtype=np.uint8))
        first.wait(WAIT_S)
        say(f"serve: set-up: first request (engine compile + first run) "
            f"{time.perf_counter() - t0:.3f} s")

        sent = [(x, acc.submit(SLOT, x, priority=lane))
                for x, lane in _requests(rng, cfg.n_features)]
        rows = _check_served(twin, twin_a, cfg, model_a, sent, "serve")
        say(f"serve: PASS {len(sent)} requests ({rows} rows, lanes "
            f"{'/'.join(LANES)}) == dense oracle == XLA twin, bit-exact")

        # hot swap with traffic queued: requests submitted before the
        # swap drain under model A, the ones after it run model B
        queued = [(x, acc.submit(SLOT, x, priority=lane))
                  for x, lane in _requests(rng, cfg.n_features)]
        acc.load(SLOT, blob_b, provenance="smoke:swap")
        after = [(x, acc.submit(SLOT, x, priority=lane))
                 for x, lane in _requests(rng, cfg.n_features)]
        _check_served(twin, twin_a, cfg, model_a, queued, "pre-swap")
        _check_served(twin, twin_b, cfg, model_b, after, "post-swap")
    finally:
        acc.stop()
    n_compiled = acc.compile_cache_size()
    require(n_compiled == 1,
            f"engine compiled {n_compiled} variants across the swap")
    say(f"serve: PASS hot-swap under traffic: {len(queued)} queued requests "
        f"answered by model A, {len(after)} later ones by model B; "
        f"compile_cache_size() == {n_compiled}")
    return {"engine": engine.name, "implementation": engine.implementation,
            "compile_cache_size": n_compiled}


def _train_data(spec, n_clauses: int, steps: int):
    b = TRAIN_BATCH
    x, y, booler = booleanized_tm_dataset(spec, b * steps, seed=SEED)
    cfg = TMConfig(n_classes=spec.n_classes, n_clauses=n_clauses,
                   n_features=booler.n_boolean_features)
    batches = [(x[s * b:(s + 1) * b], y[s * b:(s + 1) * b])
               for s in range(steps)]
    return cfg, batches


def _train(worker: RecalWorker, batches) -> np.ndarray:
    for xb, yb in batches:
        worker.fine_tune(xb, yb)
    return np.asarray(worker.state)


def recal_phase(spec, *, n_clauses: int, steps: int = 3) -> dict:
    """``steps`` RecalWorker updates on the auto-selected train engine,
    which must be ``packed``, against the ``reference`` engine."""
    cfg, batches = _train_data(spec, n_clauses, steps)
    key = jax.random.key(SEED)
    fast = RecalWorker(cfg, key=key)
    say(f"recal: train engine={fast.train_engine} dataset={spec.name} "
        f"M={cfg.n_classes} C={cfg.n_clauses} F={cfg.n_features} "
        f"batch={TRAIN_BATCH} steps={steps}")
    require(fast.train_engine == "packed",
            f"auto-selected train engine is {fast.train_engine!r}")
    start = np.asarray(fast.state)
    t0 = time.perf_counter()
    got = _train(fast, batches)
    say(f"recal: set-up: {steps} packed steps incl. compile "
        f"{time.perf_counter() - t0:.3f} s")
    want = _train(RecalWorker(cfg, key=key, train_engine="reference"),
                  batches)
    require(not np.array_equal(got, start), "training left the state as-is")
    require(np.array_equal(got, want), "packed state != reference state")
    say(f"recal: PASS packed == reference after {steps} steps "
        f"({int((got != start).sum())} TA states moved)")
    return {"train_engine": fast.train_engine, "steps": steps}


def mesh_phase(cfg: TMConfig, model, spec, *, n_clauses: int,
               implementation=None, steps: int = 2) -> dict:
    """The Fig-7 split across chips: the ``sharded`` serving engine and
    the ``sharded`` train engine on a (data, model) mesh, against the
    oracle, the one-chip ``popcount`` engine and the ``packed`` train
    engine."""
    mesh = make_mesh((2, 2), ("data", "model"))
    acc = Accelerator.for_models([model], batch_words=4, mesh=mesh)
    say(f"mesh: engine={acc.engine.name} mesh={dict(mesh.shape)} "
        f"plan={acc.plan.as_dict()}")
    require(acc.engine.name == "sharded",
            f"mesh auto-selected {acc.engine.name!r}, not 'sharded'")
    one = make_engine("popcount", acc.plan, implementation=implementation)
    one_prog = one.program(model)
    acc.load(SLOT, acc.compile(model).to_bytes())
    rng = np.random.default_rng(SEED)
    acc.start()
    try:
        sent = [(x, acc.submit(SLOT, x, priority=lane))
                for x, lane in _requests(rng, cfg.n_features)]
        rows = _check_served(one, one_prog, cfg, model, sent, "mesh")
    finally:
        acc.stop()
    require(acc.compile_cache_size() == 1, "sharded engine recompiled")
    say(f"mesh: PASS sharded serving, {rows} rows == dense oracle == "
        f"one-chip popcount ({one.implementation}), bit-exact")

    tcfg, batches = _train_data(spec, n_clauses, steps)
    key = jax.random.key(SEED)
    sharded = RecalWorker(tcfg, key=key, mesh=mesh)
    require(sharded.train_engine == "sharded",
            f"mesh auto-selected train engine {sharded.train_engine!r}")
    got = _train(sharded, batches)
    want = _train(RecalWorker(tcfg, key=key, train_engine="packed"), batches)
    require(np.array_equal(got, want), "sharded train state != packed")
    say(f"mesh: PASS sharded train engine == packed after {steps} steps")
    return {"engine": acc.engine.name, "mesh": dict(mesh.shape)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase, on a 2x2 mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {device}); "
              f"refusing to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    say(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})")
    say(f"compile cache: {enable_compile_cache()}")

    spec = TM_DATASETS["mnist"]
    if args.chips == 4:
        cfg, model = synthetic_mnist_scale(0)
        mesh_phase(cfg, model, spec, n_clauses=spec.n_clauses)
    else:
        (cfg, model_a), (_, model_b) = (synthetic_mnist_scale(s)
                                        for s in (0, 1))
        say(f"model: MNIST scale M={cfg.n_classes} C={cfg.n_clauses} "
            f"F={cfg.n_features}, {model_a.n_instructions} and "
            f"{model_b.n_instructions} instructions (seeds 0 and 1)")
        serve_phase(cfg, (model_a, model_b))
        recal_phase(spec, n_clauses=spec.n_clauses)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
