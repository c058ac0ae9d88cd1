"""Multi-tenant batched serving subsystem: bit-exactness vs the dense
oracle, hot swap under traffic with zero recompilation, batching/demux,
capacity guards and metrics."""

import asyncio
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import TMConfig, batch_class_sums, state_from_actions
from repro.core.compress import encode
from repro.serve_tm import (
    Batcher,
    DeadlineExceeded,
    EngineFault,
    Overloaded,
    PRIORITIES,
    RequestHandle,
    ServeCapacity,
    TMServer,
)
from repro.serve_tm import batching
from repro.serve_tm import scheduler as sched_mod

BACKENDS = ("interp", "plan", "sharded", "popcount")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CAP = ServeCapacity(
    instruction_capacity=1024, feature_capacity=128, class_capacity=16,
    clause_capacity=32, include_capacity=24, batch_words=2,
)


def _random_model(rng, M, C, F, density=0.05):
    cfg = TMConfig(n_classes=M, n_clauses=C, n_features=F)
    acts = rng.random((M, C, 2 * F)) < density
    return cfg, acts, encode(cfg, acts)


def _oracle_sums(cfg, acts, X):
    return np.asarray(
        batch_class_sums(cfg, state_from_actions(cfg, acts), jnp.asarray(X))
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_class_sums_bit_exact(backend):
    rng = np.random.default_rng(0)
    cfg, acts, model = _random_model(rng, 5, 12, 40)
    server = TMServer(CAP, backend=backend)
    server.register("m", model)
    X = rng.integers(0, 2, (50, 40)).astype(np.uint8)
    assert (server.class_sums("m", X) == _oracle_sums(cfg, acts, X)).all()
    assert (
        server.infer("m", X) == _oracle_sums(cfg, acts, X).argmax(1)
    ).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_hot_swap_under_traffic_zero_recompile(backend):
    """The acceptance criterion: swaps change class count AND feature
    count mid-traffic; queued requests drain under the model they were
    submitted against; the engine never recompiles."""
    rng = np.random.default_rng(1)
    cases = [(5, 12, 40), (3, 8, 24), (7, 10, 56)]
    server = TMServer(CAP, backend=backend)
    checks = []  # (handle, expected)
    for i, (M, C, F) in enumerate(cases):
        cfg, acts, model = _random_model(rng, M, C, F)
        server.register("slot", model)  # drains any queued old-F traffic
        for rows in (7, CAP.batch_capacity + 5, 1):
            x = rng.integers(0, 2, (rows, F)).astype(np.uint8)
            checks.append(
                (server.submit("slot", x),
                 _oracle_sums(cfg, acts, x).argmax(1))
            )
        if i == len(cases) - 1:
            server.flush()
    for handle, expected in checks:
        assert handle.done
        assert (handle.result() == expected).all()
    assert server.compile_cache_size() == 1
    assert server.metrics.swaps == len(cases)


@pytest.mark.parametrize("backend", BACKENDS)
def test_multi_tenant_demux(backend):
    rng = np.random.default_rng(2)
    cfg_a, acts_a, model_a = _random_model(rng, 4, 10, 32)
    cfg_b, acts_b, model_b = _random_model(rng, 6, 8, 48)
    server = TMServer(CAP, backend=backend)
    server.register("a", model_a)
    server.register("b", model_b)
    checks = []
    for i in range(12):  # interleave tenants, varied request sizes
        slot, cfg, acts = (("a", cfg_a, acts_a), ("b", cfg_b, acts_b))[i % 2]
        x = rng.integers(0, 2, (1 + i, cfg.n_features)).astype(np.uint8)
        checks.append(
            (server.submit(slot, x), _oracle_sums(cfg, acts, x).argmax(1))
        )
    server.flush()
    for handle, expected in checks:
        assert (handle.result() == expected).all()
    assert server.compile_cache_size() == 1


def test_request_spans_batches():
    rng = np.random.default_rng(3)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    server = TMServer(CAP, backend="plan")
    server.register("m", model)
    rows = 2 * CAP.batch_capacity + 3  # forces 3 engine batches
    x = rng.integers(0, 2, (rows, 32)).astype(np.uint8)
    preds = server.infer("m", x)
    assert (preds == _oracle_sums(cfg, acts, x).argmax(1)).all()
    assert server.metrics.batches == 3


def test_partial_word_padding():
    """B == 1 and B == 33 exercise partial 32-datapoint-word padding."""
    rng = np.random.default_rng(4)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    server = TMServer(CAP, backend="interp")
    server.register("m", model)
    for rows in (1, 33):
        x = rng.integers(0, 2, (rows, 32)).astype(np.uint8)
        assert (
            server.infer("m", x) == _oracle_sums(cfg, acts, x).argmax(1)
        ).all()
    # 1-D convenience submit
    x1 = rng.integers(0, 2, 32).astype(np.uint8)
    assert server.infer("m", x1).shape == (1,)


@pytest.mark.parametrize("backend", BACKENDS)
def test_capacity_guards(backend):
    rng = np.random.default_rng(5)
    server = TMServer(CAP, backend=backend)
    _, _, too_many_classes = _random_model(rng, 20, 4, 16)
    with pytest.raises(ValueError, match="class_capacity"):
        server.register("m", too_many_classes)
    _, _, too_many_features = _random_model(rng, 2, 4, 300)
    with pytest.raises(ValueError, match="capacity"):
        server.register("m", too_many_features)


def test_unknown_slot_wrong_features_and_pending_result():
    rng = np.random.default_rng(6)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    server = TMServer(CAP, backend="plan")
    with pytest.raises(KeyError, match="no model registered"):
        server.submit("ghost", np.zeros((1, 32), np.uint8))
    server.register("m", model)
    with pytest.raises(ValueError, match="features"):
        server.submit("m", np.zeros((1, 16), np.uint8))
    with pytest.raises(ValueError, match="Boolean"):
        server.submit("m", np.full((1, 32), 2, np.uint8))
    h = server.submit("m", np.zeros((4, 32), np.uint8))
    with pytest.raises(RuntimeError, match="flush"):
        h.result()
    server.flush()
    assert h.result().shape == (4,)


def test_metrics_summary():
    rng = np.random.default_rng(7)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    server = TMServer(CAP, backend="plan")
    server.register("m", model)
    for _ in range(5):
        server.submit("m", rng.integers(0, 2, (10, 32)).astype(np.uint8))
    server.flush()
    s = server.metrics.summary()
    assert s["rows"] == 50 and s["requests_completed"] == 5
    assert s["swaps"] == 1 and 0 < s["fill_ratio"] <= 1
    assert s["throughput_dps"] > 0
    assert {"p50", "p95", "p99"} <= set(s["engine_us"])
    assert s["request_latency_us"]["p50"] > 0


def test_request_latency_summary_reads_the_lane_lists():
    """``request_latency_us`` is over every completed request, read from
    the per-lane latencies (one list per request, not two)."""
    from repro.serve_tm import ServeMetrics

    m = ServeMetrics()
    lat = {"critical": [0.001, 0.004, 0.002], "low": [0.010, 0.003]}
    for lane, xs in lat.items():
        for t in xs:
            m.record_lane_completion(lane, 0.0, t)
    every = np.concatenate([np.asarray(xs) for xs in lat.values()])
    got = m.summary()["request_latency_us"]
    for q in (50, 95, 99):
        assert got[f"p{q}"] == pytest.approx(np.percentile(every, q) * 1e6)
    assert not hasattr(m, "request_latency_s")


@pytest.mark.parametrize("backend", BACKENDS)
def test_private_jit_cache_per_executor(backend):
    """Two live engines of the SAME backend must count compilations
    independently (the compile_cache_size()==1 contract is per instance
    — this is what _private_jit guarantees, now including sharded)."""
    rng = np.random.default_rng(8)
    servers = [TMServer(CAP, backend=backend) for _ in range(2)]
    for i, server in enumerate(servers):
        cfg, acts, model = _random_model(rng, 3 + i, 8, 24 + 8 * i)
        server.register("m", model)
        x = rng.integers(0, 2, (9, cfg.n_features)).astype(np.uint8)
        assert (
            server.infer("m", x) == _oracle_sums(cfg, acts, x).argmax(1)
        ).all()
    for server in servers:
        assert server.compile_cache_size() == 1
    assert servers[0].executor._fn is not servers[1].executor._fn


def test_staging_buffer_is_reused_across_flushes():
    """The flush path packs requests straight into the engine's
    preallocated staging array — no per-batch feature allocation."""
    rng = np.random.default_rng(9)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    server = TMServer(CAP, backend="popcount")
    server.register("m", model)
    staging = server.executor.staging
    assert staging.shape == (CAP.batch_capacity, CAP.feature_capacity)
    for _ in range(3):
        x = rng.integers(0, 2, (11, 32)).astype(np.uint8)
        assert (
            server.infer("m", x) == _oracle_sums(cfg, acts, x).argmax(1)
        ).all()
        # same preallocated buffer, zero-padded beyond the request rows
        assert server.executor.staging is staging
        assert (staging[11:] == 0).all() and (staging[:11, 32:] == 0).all()
    # an OFFSET view of the staging buffer must not be mistaken for a
    # fully-staged batch (it gets detached and restaged, not aliased)
    staging[:20, :32] = rng.integers(0, 2, (20, 32), dtype=np.uint8)
    view = staging[5:16, :32]
    expected = _oracle_sums(cfg, acts, view.copy())
    assert (server.executor.class_sums(
        server.registry.get("m").program, view) == expected).all()


def test_batcher_packs_into_staging_view():
    b = Batcher(64)
    h = RequestHandle(0, "s", 10)
    b.enqueue(h, np.ones((10, 4), np.uint8))
    out = np.full((64, 8), 7, np.uint8)  # stale garbage must be cleared
    X, spans = b.next_batch("s", out=out)
    assert X.shape == (10, 4) and np.shares_memory(X, out)
    assert (out[:10, :4] == 1).all() and (out[10:] == 0).all()
    assert (out[:10, 4:] == 0).all()
    b.enqueue(RequestHandle(1, "s", 2), np.ones((2, 4), np.uint8))
    with pytest.raises(ValueError, match="too small"):
        b.next_batch("s", out=np.zeros((8, 4), np.uint8))


def test_batcher_coalesces_and_splits():
    b = Batcher(64)
    h1, h2, h3 = (RequestHandle(i, "s", n) for i, n in ((0, 40), (1, 40), (2, 5)))
    b.enqueue(h1, np.zeros((40, 4), np.uint8))
    b.enqueue(h2, np.ones((40, 4), np.uint8))
    b.enqueue(h3, np.zeros((5, 4), np.uint8))
    X, spans = b.next_batch("s")
    assert X.shape[0] == 64  # h1 whole + h2 head
    assert [(s[1], s[2], s[3]) for s in spans] == [(0, 40, 0), (40, 64, 0)]
    X2, spans2 = b.next_batch("s")
    assert X2.shape[0] == 21  # h2 tail + h3
    assert spans2[0][3] == 24  # resumes at row 24 of h2
    assert b.pending_rows("s") == 0
    with pytest.raises(ValueError, match="no pending"):
        b.next_batch("s")
    with pytest.raises(ValueError, match="multiple"):
        Batcher(33)


# ---------------------------------------------------------------------------
# the scheduler-owned continuous-batching runtime (priority lanes, EDF,
# deadlines, admission control, the async front door)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_scheduler_async_path_bit_exact(backend):
    """All four engines stay bit-exact when traffic rides the async front
    door (async_submit -> loop-formed batches -> async_result), with the
    no-recompile invariant held per scheduler-formed batch."""
    rng = np.random.default_rng(7)
    cfg, acts, model = _random_model(rng, 5, 12, 40)
    server = TMServer(CAP, backend=backend, max_wait_ms=0.5)
    server.register("m", model)
    server.start()
    try:
        async def drive():
            handles, blocks = [], []
            for i, pr in enumerate(PRIORITIES * 2):
                x = rng.integers(0, 2, (3 + i, 40)).astype(np.uint8)
                h = await server.async_submit("m", x, priority=pr)
                handles.append(h)
                blocks.append(x)
            return [
                (await h.async_result(timeout=30.0), x)
                for h, x in zip(handles, blocks)
            ]

        for preds, x in asyncio.run(drive()):
            assert (preds == _oracle_sums(cfg, acts, x).argmax(1)).all()
        assert server.compile_cache_size() == 1
        lanes = server.metrics.summary()["lanes"]
        assert all(lanes[p]["completed"] == 2 for p in PRIORITIES)
        assert all(lanes[p]["shed"] == 0 for p in PRIORITIES)
    finally:
        server.stop()


@pytest.mark.parametrize("backend", ("plan", "popcount"))
def test_live_scheduler_hot_swap_and_rollback_drain(backend):
    """Hot-swap (register) and rollback land while the scheduler loop is
    live with a queued backlog: the backlog completes under the OLD
    program (the lock is held across drain + install), and the engine
    never recompiles across either transition."""
    rng = np.random.default_rng(8)
    cfg_a, acts_a, model_a = _random_model(rng, 5, 12, 40)
    cfg_b, acts_b, model_b = _random_model(rng, 3, 8, 24)
    server = TMServer(CAP, backend=backend, max_wait_ms=0.2)
    server.register("slot", model_a)
    server.start()
    try:
        # stall the loop on the scheduler lock so a multi-batch backlog
        # builds, then swap: register must drain it under model A first
        with server.scheduler.lock:
            xs = [
                rng.integers(0, 2, (CAP.batch_capacity + 3, 40)).astype(
                    np.uint8
                )
                for _ in range(2)
            ]
            handles = [server.submit("slot", x) for x in xs]
            server.register("slot", model_b)
        for h, x in zip(handles, xs):
            assert (
                h.wait(timeout=30.0)
                == _oracle_sums(cfg_a, acts_a, x).argmax(1)
            ).all()
        # same discipline for rollback: queued model-B traffic finishes
        # under B, then A's buffers come back
        with server.scheduler.lock:
            xb = rng.integers(0, 2, (CAP.batch_capacity + 1, 24)).astype(
                np.uint8
            )
            hb = server.submit("slot", xb)
            server.rollback("slot")
        assert (
            hb.wait(timeout=30.0) == _oracle_sums(cfg_b, acts_b, xb).argmax(1)
        ).all()
        # post-rollback the loop serves under model A again, no flush()
        xa = rng.integers(0, 2, (9, 40)).astype(np.uint8)
        ha = server.submit("slot", xa)
        assert (
            ha.wait(timeout=30.0) == _oracle_sums(cfg_a, acts_a, xa).argmax(1)
        ).all()
        assert server.compile_cache_size() == 1
    finally:
        server.stop()


def test_batch_formation_property_priority_and_expiry():
    """Property: scheduler batch formation never violates strict priority
    order within a batch and never includes an expired request; every
    past-deadline request ends shed, everything else completes."""
    pytest.importorskip("hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.serve_tm.batching import PRIORITY_RANK

    reqs = st.lists(
        st.tuples(
            st.integers(0, 3),                      # priority index
            st.integers(1, 12),                     # rows
            st.sampled_from(("past", "soon", "none")),
        ),
        min_size=1,
        max_size=24,
    )

    @given(reqs)
    @settings(max_examples=60, deadline=None)
    def check(spec):
        now = 1_000.0  # synthetic clock injected into next_batch
        b = Batcher(64)
        handles = []
        for i, (pi, rows, dl) in enumerate(spec):
            deadline = {"past": now - 1.0, "soon": now + 60.0, "none": None}[dl]
            h = RequestHandle(
                i, "s", rows, priority=PRIORITIES[pi], deadline=deadline
            )
            b.enqueue(h, np.zeros((rows, 8), np.uint8))
            handles.append((h, dl))
        while b.pending_rows("s"):
            X, spans = b.next_batch("s", now=now)
            ranks = [PRIORITY_RANK[h.priority] for h, _, _, _ in spans]
            assert ranks == sorted(ranks)
            for h, lo, hi, _ in spans:
                assert not h.expired
                assert h.deadline is None or h.deadline > now
            assert X.shape[0] == sum(hi - lo for _, lo, hi, _ in spans)
            # the engine's answer for the batch completes its requests
            Batcher.demux(spans, np.zeros(X.shape[0], np.int32))
        for h, dl in handles:
            assert h.status == ("expired" if dl == "past" else "done")

    check()


def test_demux_records_completions_before_waking_waiters():
    """A caller that reads metrics as soon as ``result()`` returns finds
    its request counted: ``demux``'s record hook runs before any waiter
    of the batch can wake."""
    b = Batcher(32)
    h_done = RequestHandle(0, "s", 4, priority=PRIORITIES[0])
    h_part = RequestHandle(1, "s", 40, priority=PRIORITIES[0])
    b.enqueue(h_done, np.zeros((4, 8), np.uint8))
    b.enqueue(h_part, np.zeros((40, 8), np.uint8))
    X, spans = b.next_batch("s")
    seen = []

    def record(completed):
        seen.extend(completed)
        assert not any(h._terminal_evt.is_set() for h in completed)

    preds = np.ones(X.shape[0], np.int32)
    assert Batcher.demux(spans, preds, record=record) == (1, 0)
    assert seen == [h_done] and h_done._terminal_evt.is_set()
    assert (h_done.result() == 1).all()
    assert not h_part.done and not h_part._terminal_evt.is_set()


class _CountingLoop:
    """Stands in for a waiter's event loop: counts the cross-thread
    callbacks it is sent and runs each at once."""

    def __init__(self):
        self.calls = 0

    def call_soon_threadsafe(self, callback, *args):
        self.calls += 1
        callback(*args)


def _one_row_batch(n):
    """``n`` one-row requests formed into one batch -> (handles, spans,
    preds)."""
    b = Batcher(32)
    handles = [RequestHandle(i, "s", 1) for i in range(n)]
    for h in handles:
        b.enqueue(h, np.zeros((1, 8), np.uint8))
    X, spans = b.next_batch("s")
    return handles, spans, np.arange(X.shape[0], dtype=np.int32)


def _await_on(handle, loop):
    """Register an asyncio waiter for ``handle`` as if awaited on
    ``loop``; -> its event."""
    evt = asyncio.Event()
    handle._async_waiters.append((loop, evt))
    return evt


def test_demux_wakes_one_asyncio_loop_once_per_batch():
    """Eight one-row requests awaited from one asyncio loop, completed
    by one batch on another thread, cost one cross-thread wake; every
    awaiter still gets its own row."""
    rng = np.random.default_rng(31)
    cfg, acts, model = _random_model(rng, 4, 8, 32)
    server = TMServer(CAP, backend="plan")
    server.register("m", model)
    xs = [rng.integers(0, 2, (1, 32)).astype(np.uint8) for _ in range(8)]

    async def drive():
        handles = [server.submit("m", x) for x in xs]
        tasks = [asyncio.ensure_future(h.async_result(timeout=30.0))
                 for h in handles]
        while not all(h._async_waiters for h in handles):
            await asyncio.sleep(0)
        await asyncio.get_running_loop().run_in_executor(None, server.flush)
        return await asyncio.gather(*tasks)

    for preds, x in zip(asyncio.run(drive()), xs):
        assert (preds == _oracle_sums(cfg, acts, x).argmax(1)).all()
    s = server.metrics.summary()
    assert s["batches"] == 1 and s["requests_completed"] == 8
    assert s["completion_wakes"] == 1


def test_demux_wakes_each_loop_once_in_completion_order():
    """Handles awaited from two loops: exactly two callbacks, one per
    loop, each setting its own loop's events in completion order."""
    handles, spans, preds = _one_row_batch(6)
    loops = (_CountingLoop(), _CountingLoop())
    order = []
    for i, h in enumerate(handles):
        evt = _await_on(h, loops[i % 2])
        evt.set = (lambda rid=h.rid: order.append(rid))
    assert Batcher.demux(spans, preds) == (6, 2)
    assert [lp.calls for lp in loops] == [1, 1]
    assert order == [0, 2, 4, 1, 3, 5]
    assert [int(h.result()[0]) for h in handles] == list(range(6))


def test_demux_sync_waiter_wakes_without_a_callback():
    """A handle waited on with the blocking ``wait()`` and no asyncio
    waiter wakes from its threading event and adds no callback."""
    handles, spans, preds = _one_row_batch(1)
    got = []
    waiter = threading.Thread(
        target=lambda: got.append(handles[0].wait(timeout=30.0)))
    waiter.start()
    assert Batcher.demux(spans, preds) == (1, 0)
    waiter.join(timeout=30.0)
    assert not waiter.is_alive() and got and int(got[0][0]) == 0


def test_demux_skips_a_closed_loop_and_wakes_the_others():
    """A waiter whose loop has closed is skipped without raising and is
    not counted; the other waiters of the batch still wake."""
    handles, spans, preds = _one_row_batch(3)
    closed = asyncio.new_event_loop()
    closed.close()
    live = _CountingLoop()
    dead_evt = _await_on(handles[0], closed)
    live_evts = [_await_on(h, live) for h in handles[1:]]
    assert Batcher.demux(spans, preds) == (3, 1)
    assert live.calls == 1 and all(e.is_set() for e in live_evts)
    assert not dead_evt.is_set()
    assert all(h._terminal_evt.is_set() for h in handles)


def test_fail_batch_fails_every_handle_through_signal_terminal(monkeypatch):
    """A raising batch body fails all of its handles with one
    ``signal_terminal`` call; their async awaiters re-raise
    ``EngineFault``."""
    rng = np.random.default_rng(37)
    _, _, model = _random_model(rng, 4, 8, 32)
    server = TMServer(CAP, backend="plan")
    server.register("m", model)
    real = server.executor

    class _Boom:
        def __getattr__(self, name):
            return getattr(real, name)

        def class_sums(self, prog, xx):
            raise RuntimeError("injected engine fault")

    calls = []

    def spy(handles):
        calls.append(list(handles))
        return batching.signal_terminal(handles)

    monkeypatch.setattr(sched_mod, "signal_terminal", spy)
    server.executor = _Boom()
    xs = [rng.integers(0, 2, (1, 32)).astype(np.uint8) for _ in range(4)]

    async def drive():
        handles = [server.submit("m", x) for x in xs]
        tasks = [asyncio.ensure_future(h.async_result(timeout=30.0))
                 for h in handles]
        while not all(h._async_waiters for h in handles):
            await asyncio.sleep(0)
        await asyncio.get_running_loop().run_in_executor(None, server.flush)
        return handles, await asyncio.gather(*tasks, return_exceptions=True)

    handles, results = asyncio.run(drive())
    assert calls == [handles]
    for h, r in zip(handles, results):
        assert h.failed and isinstance(r, EngineFault) and r.slot == "m"
        assert isinstance(r.cause, RuntimeError)


def test_async_submit_admission_control_overload():
    """Admission control: the low lane rejects once its queue-depth
    budget fills, with the structured Overloaded fields; critical keeps
    admitting under the exact same backlog."""
    rng = np.random.default_rng(9)
    cfg, acts, model = _random_model(rng, 4, 8, 32)
    server = TMServer(
        CAP,
        backend="plan",
        lane_depth_rows={"low": CAP.batch_capacity},
    )
    server.register("m", model)
    x_full = rng.integers(0, 2, (CAP.batch_capacity, 32)).astype(np.uint8)
    x_one = rng.integers(0, 2, (1, 32)).astype(np.uint8)

    async def drive():
        await server.async_submit("m", x_full, priority="low")
        with pytest.raises(Overloaded) as ei:
            await server.async_submit("m", x_one, priority="low")
        err = ei.value
        assert (err.slot, err.priority) == ("m", "low")
        assert err.pending_rows == CAP.batch_capacity
        assert err.limit_rows == CAP.batch_capacity
        # critical still has headroom under the same backlog
        return await server.async_submit("m", x_one, priority="critical")

    h = asyncio.run(drive())
    server.flush()
    assert (h.result() == _oracle_sums(cfg, acts, x_one).argmax(1)).all()
    s = server.metrics.summary()
    assert s["admission_rejects"] == 1
    assert s["lanes"]["low"]["rejected"] == 1
    assert s["lanes"]["critical"]["rejected"] == 0
    with pytest.raises(KeyError):
        asyncio.run(server.async_submit("nope", x_one))


def test_concurrent_submits_race_live_loop_no_drops():
    """Submit-side heap pushes run on caller threads while the loop
    thread forms batches; without the batcher lock heapq's peek-then-pop
    can pop a freshly-pushed earlier-deadline entry and silently drop it
    (its handle never reaches a terminal state).  Hammer a live loop
    from several threads with interleaved deadline/deadline-less
    requests so lane-heap roots keep re-ordering: every handle must
    complete bit-exactly and every rid must be unique."""
    rng = np.random.default_rng(11)
    cfg, acts, model = _random_model(rng, 4, 8, 32)
    server = TMServer(CAP, backend="plan", max_wait_ms=0.2)
    server.register("m", model)
    server.start()
    results = []
    mu = threading.Lock()
    n_threads = 4
    start = threading.Barrier(n_threads)

    def hammer(seed):
        trng = np.random.default_rng(seed)
        start.wait()
        for i in range(25):
            x = trng.integers(0, 2, (1 + i % 3, 32)).astype(np.uint8)
            # far-future deadlines interleaved with deadline-less so
            # every push contends for the heap root mid-formation
            tmo = None if i % 2 else 30_000.0
            h = server.submit(
                "m", x, priority=PRIORITIES[i % 4], timeout_ms=tmo
            )
            with mu:
                results.append((h, x))

    threads = [
        threading.Thread(target=hammer, args=(100 + t,))
        for t in range(n_threads)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for h, x in results:
            assert (
                h.wait(timeout=30.0) == _oracle_sums(cfg, acts, x).argmax(1)
            ).all()
        assert server.compile_cache_size() == 1
    finally:
        server.stop()
    rids = [h.rid for h, _ in results]
    assert len(set(rids)) == len(rids)
    lanes = server.metrics.summary()["lanes"]
    assert sum(lanes[p]["shed"] for p in PRIORITIES) == 0


def test_scheduler_loop_survives_batch_exception():
    """One failing loop iteration must not kill the tm-scheduler daemon
    thread (a dead loop strands every pending request): the error is
    logged, the loop keeps running, and the next iteration serves the
    queue."""
    rng = np.random.default_rng(13)
    cfg, acts, model = _random_model(rng, 4, 8, 32)
    server = TMServer(CAP, backend="plan", max_wait_ms=0.2)
    server.register("m", model)
    real = server.scheduler.run_slot_batch
    calls = {"n": 0}

    def flaky(slot):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected batch failure")
        return real(slot)

    server.scheduler.run_slot_batch = flaky
    try:
        server.start()
        x = rng.integers(0, 2, (5, 32)).astype(np.uint8)
        h = server.submit("m", x)
        assert (
            h.wait(timeout=30.0) == _oracle_sums(cfg, acts, x).argmax(1)
        ).all()
        assert server.scheduler.running
        assert calls["n"] >= 2
    finally:
        server.scheduler.run_slot_batch = real
        server.stop()


def test_admission_and_enqueue_atomic_under_contention():
    """The depth check and the enqueue are one atomic section: N racing
    async submitters cannot all pass the same check and collectively
    exceed the lane budget.  With no scheduler draining, exactly
    budget/rows_each submits are admitted, the rest get Overloaded."""
    rng = np.random.default_rng(12)
    _, _, model = _random_model(rng, 4, 8, 32)
    limit = CAP.batch_capacity
    server = TMServer(CAP, backend="plan", lane_depth_rows={"low": limit})
    server.register("m", model)
    rows_each = limit // 4
    n_threads = 8  # 2x oversubscribed: exactly half must be rejected
    start = threading.Barrier(n_threads)
    outcomes = []
    mu = threading.Lock()

    def submitter(seed):
        x = np.random.default_rng(seed).integers(
            0, 2, (rows_each, 32)
        ).astype(np.uint8)
        start.wait()
        try:
            asyncio.run(server.async_submit("m", x, priority="low"))
            ok = True
        except Overloaded:
            ok = False
        with mu:
            outcomes.append(ok)

    threads = [
        threading.Thread(target=submitter, args=(200 + t,))
        for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    admitted = sum(outcomes)
    assert admitted == limit // rows_each
    assert server.batcher.pending_rows("m", "low") == limit
    assert server.metrics.summary()["lanes"]["low"]["rejected"] == (
        n_threads - admitted
    )
    server.flush()  # don't strand the admitted backlog


def test_deadline_shed_and_expired_terminal_state():
    """A request whose deadline passes before service is shed, lands in
    the expired terminal state, and raises DeadlineExceeded from both
    result() and wait(); the lane accounting separates it from the
    in-SLO completion sharing its lane."""
    rng = np.random.default_rng(10)
    cfg, acts, model = _random_model(rng, 4, 8, 32)
    server = TMServer(CAP, backend="plan")
    server.register("m", model)
    x = rng.integers(0, 2, (6, 32)).astype(np.uint8)
    h_ok = server.submit("m", x)
    h_dead = server.submit("m", x, timeout_ms=0.0)
    server.flush()
    assert (h_ok.result() == _oracle_sums(cfg, acts, x).argmax(1)).all()
    assert h_dead.status == "expired" and h_dead.expired
    with pytest.raises(DeadlineExceeded) as ei:
        h_dead.result()
    assert (ei.value.rid, ei.value.slot) == (h_dead.rid, "m")
    assert ei.value.priority == "normal"
    with pytest.raises(DeadlineExceeded):
        h_dead.wait(timeout=5.0)
    s = server.metrics.summary()
    assert s["sheds"] == 1
    assert s["lanes"]["normal"]["shed"] == 1
    assert s["lanes"]["normal"]["completed"] == 1
    assert s["lanes"]["normal"]["slo_attainment"] == 0.5


def test_pending_result_error_names_driver_and_slot():
    """Satellite regression: the pending-result error names whichever
    driver owns the request (sync flush vs scheduler loop) and the slot."""
    rng = np.random.default_rng(11)
    _, _, model = _random_model(rng, 4, 8, 32)
    server = TMServer(CAP, backend="plan")
    server.register("m", model)
    h = server.submit("m", rng.integers(0, 2, (4, 32)).astype(np.uint8))
    with pytest.raises(RuntimeError, match=r"slot 'm'.*TMServer\.flush\(\)"):
        h.result()
    server.flush()
    h2 = RequestHandle(99, "edge", 4)
    h2.driver = "scheduler"
    with pytest.raises(RuntimeError, match=r"slot 'edge'.*async_result\(\)"):
        h2.result()


def test_scheduler_lifecycle_idempotent_and_stop_drains():
    rng = np.random.default_rng(12)
    cfg, acts, model = _random_model(rng, 4, 8, 32)
    server = TMServer(CAP, backend="plan", max_wait_ms=0.2)
    server.register("m", model)
    server.start()
    server.start()  # idempotent
    assert server.scheduler_running
    with server.scheduler.lock:  # enqueue while the loop can't serve
        x = rng.integers(0, 2, (5, 32)).astype(np.uint8)
        h = server.submit("m", x)
    server.stop()  # drain=True: nothing admitted is stranded
    assert not server.scheduler_running
    assert (h.result() == _oracle_sums(cfg, acts, x).argmax(1)).all()
    # sync submit after stop reverts to the flush driver
    h2 = server.submit("m", x)
    assert h2.driver == "flush"
    server.flush()
    assert h2.done


def test_executors_shim_deprecation_fires_once():
    """Satellite 1: importing the legacy executors shim (or calling
    make_executor) emits a real DeprecationWarning exactly once per
    process, while importing repro.serve_tm itself stays silent."""
    code = textwrap.dedent(
        """
        import warnings

        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            import repro.serve_tm                 # package import: silent
            import repro.serve_tm.executors       # shim: warns
            import repro.serve_tm.executors       # cached: no second warning
        dep = [
            w for w in rec if issubclass(w.category, DeprecationWarning)
        ]
        assert len(dep) == 1, [str(w.message) for w in rec]
        assert "repro.accel" in str(dep[0].message)

        from repro.serve_tm.executors import ServeCapacity, make_executor

        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            make_executor("interp", ServeCapacity())
        dep = [
            w for w in rec if issubclass(w.category, DeprecationWarning)
        ]
        assert len(dep) == 1, [str(w.message) for w in rec]
        assert "make_engine" in str(dep[0].message)
        print("SHIM-OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env
    )
    assert out.returncode == 0, out.stderr
    assert "SHIM-OK" in out.stdout
