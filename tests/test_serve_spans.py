"""The serving path's profiler spans (``repro.accel.spans``), read back
from a trace of a CPU run of the scheduler and the ``popcount`` engine
(XLA twin): each batch body holds its phases once each, in order, the
scheduler's sleep lies outside every batch, and ``tm.demux`` counts its
wakes.  Also the stable name of each engine's jitted step."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.accel import Accelerator, CapacityPlan, make_engine, spans
from repro.core import TMConfig
from repro.core.compress import encode

STEPS = [spans.FORM, spans.H2D, spans.LAUNCH, spans.D2H, spans.DEMUX]
BURSTS = 6


def _model(rng):
    cfg = TMConfig(n_classes=3, n_clauses=8, n_features=16)
    return encode(cfg, rng.random((3, 8, 32)) < 0.1)


def _host_events(out):
    """Per host trace line of the trace under ``out``, its ``tm.*``
    events."""
    data = ProfileData.from_file(str(next(out.rglob("*.xplane.pb"))))
    return [
        [ev for ev in ln.events if ev.name in spans.ALL]
        for p in data.planes if p.name.startswith("/host:")
        for ln in p.lines
    ]


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    """Per host trace line, its ``tm.*`` events as (name, start, end)."""
    out = tmp_path_factory.mktemp("spans")
    rng = np.random.default_rng(0)
    model = _model(rng)
    acc = Accelerator.for_models([model])
    assert (acc.engine.name, acc.engine.implementation) == ("popcount", "xla")
    acc.load("m", acc.compile(model).to_bytes())
    acc.start()
    try:
        x = rng.integers(0, 2, (4, 16), dtype=np.uint8)
        acc.submit("m", x).wait(30)  # compile outside the trace
        jax.profiler.start_trace(str(out))
        try:
            for _ in range(BURSTS):
                for h in [acc.submit("m", x) for _ in range(3)]:
                    h.wait(30)
        finally:
            jax.profiler.stop_trace()
    finally:
        acc.stop()
    found = [
        sorted(((ev.name, ev.start_ns, ev.end_ns) for ev in ln),
               key=lambda ev: (ev[1], -ev[2]))
        for ln in _host_events(out)
    ]
    return [ln for ln in found if ln]


def _batches(lines):
    return [(ln, ev) for ln in lines for ev in ln if ev[0] == spans.BATCH]


def test_every_batch_holds_each_phase_once_in_order(lines):
    batches = _batches(lines)
    assert len(batches) >= BURSTS
    for ln, (_, s, e) in batches:
        kids = [ev for ev in ln
                if ev[0] != spans.BATCH and ev[1] >= s and ev[2] <= e]
        assert [k[0] for k in kids] == STEPS
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        assert sum(k[2] - k[1] for k in kids) <= e - s


def test_the_scheduler_sleep_lies_outside_every_batch(lines):
    waits = [ev for ln in lines for ev in ln if ev[0] == spans.WAIT]
    assert waits  # the loop slept between bursts
    for _, (_, s, e) in _batches(lines):
        assert not any(ws < e and we > s for _, ws, we in waits)


def test_demux_span_counts_one_wake_per_asyncio_loop(tmp_path):
    """A batch of one-row requests awaited from one asyncio loop reads
    ``wakes=1`` on its ``tm.demux`` span."""
    rng = np.random.default_rng(2)
    model = _model(rng)
    acc = Accelerator.for_models([model])
    acc.load("m", acc.compile(model).to_bytes())
    x = rng.integers(0, 2, (1, 16), dtype=np.uint8)
    acc.infer("m", x)  # compile outside the trace

    async def drive():
        handles = [acc.submit("m", x) for _ in range(8)]
        tasks = [asyncio.ensure_future(h.async_result(timeout=30.0))
                 for h in handles]
        while not all(h._async_waiters for h in handles):
            await asyncio.sleep(0)
        await asyncio.get_running_loop().run_in_executor(None, acc.flush)
        return await asyncio.gather(*tasks)

    jax.profiler.start_trace(str(tmp_path))
    try:
        assert len(asyncio.run(drive())) == 8
    finally:
        jax.profiler.stop_trace()
    demux = [dict(ev.stats) for ln in _host_events(tmp_path) for ev in ln
             if ev.name == spans.DEMUX]
    assert [d.get("wakes") for d in demux] == [1]


def test_batch_span_names_the_positions_of_its_model(tmp_path):
    """``tm.batch`` carries the served model's patch positions: 1 for a
    plain TM, 16 for a 3x3 window on 6x6 images."""
    from repro.core.tm import PatchGeometry

    rng = np.random.default_rng(3)
    geom = PatchGeometry(6, 6, 3, 3)
    ctm = encode(TMConfig(3, 8, geom.n_features, patch=geom),
                 rng.random((3, 8, 2 * geom.n_features)) < 0.1)
    plain = _model(rng)
    acc = Accelerator.for_models([plain, ctm])
    acc.load("plain", acc.compile(plain))
    acc.load("ctm", acc.compile(ctm))
    rows = {"plain": rng.integers(0, 2, (2, 16), dtype=np.uint8),
            "ctm": rng.integers(0, 2, (2, 36), dtype=np.uint8)}
    for slot, x in rows.items():
        acc.infer(slot, x)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for slot, x in rows.items():
            acc.infer(slot, x)
    finally:
        jax.profiler.stop_trace()
    batches = [dict(ev.stats) for ln in _host_events(tmp_path) for ev in ln
               if ev.name == spans.BATCH]
    assert [(d.get("slot"), d.get("positions")) for d in batches] == [
        ("plain", 1), ("ctm", 16)]


def test_engine_steps_carry_their_names():
    plan = CapacityPlan(instruction_capacity=256, feature_capacity=16,
                        class_capacity=4, batch_words=1)
    for name in ("interp", "plan", "popcount"):
        assert make_engine(name, plan)._fn.__name__ == f"tm_{name}_step"
    engine = make_engine("popcount", plan)
    prog = engine.program(_model(np.random.default_rng(1)))
    text = engine._fn.lower(
        prog["lit_idx"], prog["last"], prog["mask_pos"], prog["mask_neg"],
        jnp.asarray(engine.staging)).as_text()
    assert "jit_tm_popcount_step" in text
