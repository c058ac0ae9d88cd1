"""The fleet's failure model: deterministic chaos injection, the
per-node circuit breaker (healthy → degraded → quarantined → half-open
probe → healthy), retry/backoff under a hard deadline budget, structured
engine faults, and failure-aware rollouts/teardown — all under injected
clocks, never wall-clock sleeps."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import TMConfig, batch_class_sums, state_from_actions
from repro.core.compress import encode
from repro.accel import CapacityPlan, TMProgram
from repro.fleet import (
    ChaosNode,
    FleetHealth,
    FleetPool,
    NodeDown,
    NoEligibleNode,
    RetryPolicy,
    RolloutAborted,
    RolloutManager,
    Router,
)
from repro.serve_tm import EngineFault, TMServer
from repro.serve_tm.schema import HEALTH_NODE_KEYS, HEALTH_STATES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CAP = CapacityPlan(
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


def _program(model, cap=CAP):
    return TMProgram(capacity=cap, model=model)


class _FakeTime:
    """One injectable clock for the breaker, the policy and its sleeps."""

    def __init__(self):
        self.t = 0.0
        self.sleeps = []  # (clock at sleep, requested duration)

    def clock(self):
        return self.t

    def sleep(self, d):
        self.sleeps.append((self.t, d))
        self.t += d


class _StubNode:
    """Minimal structural ServingNode whose submit always fails —
    drives the retry loop without touching an engine."""

    def __init__(self, advance=None):
        self.calls = 0
        self.scheduler_running = False
        self.capacity = CAP
        self._advance = advance  # simulated per-call service cost

    def submit(self, slot, x, *, priority="normal", timeout_ms=None):
        self.calls += 1
        if self._advance is not None:
            self._advance()
        raise RuntimeError("stub node always fails")

    async def async_submit(self, slot, x, *, priority="normal",
                           timeout_ms=None):
        return self.submit(slot, x, priority=priority, timeout_ms=timeout_ms)

    def flush(self):
        pass

    def infer(self, slot, x):
        return self.submit(slot, x)

    def class_sums(self, slot, x):
        raise RuntimeError("stub")

    def start(self):
        pass

    def stop(self, drain=True):
        pass

    def register(self, slot, model, provenance="install"):
        pass

    def rollback(self, slot):
        pass

    def validate_model(self, model):
        pass

    def queue_depth(self, slot=None, priority=None):
        return 0

    def metrics_snapshot(self):
        return {}

    def slots(self):
        return ["m"]

    def installed_checksum(self, slot):
        return 0

    def installed_artifact(self, slot):
        return None

    def compile_cache_size(self):
        return 1


# -- RetryPolicy: the deadline budget rule -----------------------------------


def test_retry_policy_validation_and_backoff_shape():
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError, match="multiplier"):
        RetryPolicy(backoff_multiplier=0.5)
    p = RetryPolicy(backoff_base_s=0.01, backoff_multiplier=2.0,
                    backoff_max_s=0.05)
    assert [p.backoff_s(i) for i in range(5)] == [
        0.01, 0.02, 0.04, 0.05, 0.05,  # exponential, capped
    ]


def test_retry_policy_deadline_budget_property():
    """Property: against an always-failing node, the router never tries
    more than max_attempts, every backoff sleep fits inside the
    remaining deadline budget, and the backoff sequence is exactly the
    policy's capped exponential — all under simulated time."""
    pytest.importorskip("hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    x = np.zeros((1, 4), np.uint8)

    @given(
        max_attempts=st.integers(1, 6),
        base_ms=st.floats(0.1, 50.0),
        mult=st.floats(1.0, 4.0),
        cap_ms=st.floats(0.1, 100.0),
        timeout_ms=st.one_of(st.none(), st.floats(0.1, 300.0)),
        call_cost_ms=st.floats(0.0, 30.0),
    )
    @settings(max_examples=60, deadline=None)
    def check(max_attempts, base_ms, mult, cap_ms, timeout_ms, call_cost_ms):
        ft = _FakeTime()

        def advance():
            ft.t += call_cost_ms / 1e3

        node = _StubNode(advance=advance)
        pool = FleetPool({"a": node})
        # thresholds pushed out of reach: this property is about the
        # policy arithmetic, not the breaker
        health = FleetHealth(
            pool=pool, clock=ft.clock, consecutive_failures=10 ** 9,
            min_window=10 ** 9, probe_after_s=1e9,
        )
        retry = RetryPolicy(
            max_attempts=max_attempts, backoff_base_s=base_ms / 1e3,
            backoff_multiplier=mult, backoff_max_s=cap_ms / 1e3,
            sleep=ft.sleep, clock=ft.clock,
        )
        router = Router(pool, health=health, retry=retry)
        with pytest.raises(RuntimeError, match="stub node always fails"):
            router.submit("m", x, timeout_ms=timeout_ms)
        assert 1 <= node.calls <= max_attempts
        if timeout_ms is None:
            # no deadline: the full attempt budget is spent, with one
            # backoff between each single-candidate sweep
            assert node.calls == max_attempts
            assert len(ft.sleeps) == max_attempts - 1
        else:
            deadline = timeout_ms / 1e3  # stamped at t=0
            for at, d in ft.sleeps:
                assert at + d < deadline  # never sleeps past the budget
        for i, (_, d) in enumerate(ft.sleeps):
            assert d == pytest.approx(retry.backoff_s(i))

    check()


# -- the circuit breaker ------------------------------------------------------


class _FlakySubmit(TMServer):
    """A real node whose submit fails on demand (the engine is fine)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.failing = False
        self.calls = 0

    def submit(self, slot, x, **kw):
        self.calls += 1
        if self.failing:
            raise RuntimeError("transient engine fault")
        return super().submit(slot, x, **kw)


def test_breaker_full_cycle_quarantine_probe_recover_under_fake_clock():
    """healthy → degraded → quarantined → (cooldown) → half-open probe →
    healthy, and the probe-failure edge back to quarantined — all
    transitions driven through the ROUTER, no wall-clock."""
    rng = np.random.default_rng(30)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    art = _program(model)
    bad = _FlakySubmit(CAP, engine="interp")
    ok = TMServer(CAP, engine="plan")
    for node in (bad, ok):
        node.register("m", art)
    pool = FleetPool({"bad": bad, "ok": ok})
    ft = _FakeTime()
    health = FleetHealth(
        pool=pool, consecutive_failures=2, probe_after_s=5.0,
        heartbeat_timeout_s=1e9, clock=ft.clock,
    )
    router = Router(pool, health=health,
                    retry=RetryPolicy(sleep=ft.sleep, clock=ft.clock))
    x = rng.integers(0, 2, (4, 32)).astype(np.uint8)

    bad.failing = True
    assert router.submit("m", x).routed_to == "ok"
    assert health.state("bad") == "degraded"
    assert router.submit("m", x).routed_to == "ok"
    assert health.state("bad") == "quarantined"  # consecutive threshold

    # quarantined + cooldown not elapsed: the node is not even tried
    calls = bad.calls
    assert router.submit("m", x).routed_to == "ok"
    assert bad.calls == calls

    # cooldown elapses, the node healed: ONE half-open probe closes the
    # breaker and the probe request itself is served there
    ft.t += 5.0
    bad.failing = False
    h = router.submit("m", x)
    assert h.routed_to == "bad"
    assert health.state("bad") == "healthy"
    assert health.summary()["bad"]["probes"] == 1

    # the probe-failure edge: re-quarantined, cooldown restamped
    bad.failing = True
    router.submit("m", x)
    router.submit("m", x)
    assert health.state("bad") == "quarantined"
    ft.t += 5.0
    assert health.probe_due("bad")
    assert router.submit("m", x).routed_to == "ok"  # probe fails over
    assert health.state("bad") == "quarantined"
    assert not health.probe_due("bad")  # cooldown restarted
    assert health.summary()["bad"]["probes"] == 2
    assert health.summary()["bad"]["quarantines"] == 3
    # the router mirrored failovers into the serving node's own metrics
    assert ok.metrics.failovers > 0


def test_router_all_quarantined_raises_structured_no_eligible_node():
    node = _StubNode()
    pool = FleetPool({"a": node})
    health = FleetHealth(pool=pool, probe_after_s=1e9)
    health.quarantine("a", reason="manual")
    router = Router(pool, health=health,
                    retry=RetryPolicy(sleep=lambda d: None))
    with pytest.raises(NoEligibleNode, match="quarantined or unreachable"):
        router.submit("m", np.zeros((1, 4), np.uint8))
    assert node.calls == 0


def test_heartbeat_sweep_quarantines_silent_nodes():
    ft = _FakeTime()
    health = FleetHealth(heartbeat_timeout_s=10.0, clock=ft.clock)
    health.record_success("a")
    health.record_success("b")
    ft.t = 5.0
    health.record_success("a")  # a keeps beating, b goes silent
    ft.t = 12.0
    assert health.sweep() == ["b"]
    assert health.state("b") == "quarantined"
    assert health.state("a") == "healthy"
    assert health.sweep() == []  # already quarantined: not re-flagged


def test_straggler_evict_quarantines_slow_node():
    """A node that still answers but far slower than its own history is
    routed around like a dead one (supervisor's StragglerMonitor)."""
    health = FleetHealth(consecutive_failures=10 ** 9)
    for _ in range(8):
        health.record_success("slow", latency_s=0.01)
    assert health.state("slow") == "healthy"
    n = 0
    while health.state("slow") != "quarantined" and n < 30:
        health.record_success("slow", latency_s=5.0)
        n += 1
    assert health.state("slow") == "quarantined"
    assert health.summary()["slow"]["quarantines"] == 1


def test_health_summary_matches_schema():
    health = FleetHealth()
    health.record_success("a", latency_s=0.01)
    health.record_failure("b", RuntimeError("x"))
    health.record_overload("a")
    summary = health.summary()
    assert list(summary) == ["a", "b"]
    for d in summary.values():
        assert tuple(d.keys()) == HEALTH_NODE_KEYS
        assert d["state"] in HEALTH_STATES
    assert summary["a"]["overloads"] == 1
    assert summary["b"]["consecutive_failures"] == 1


# -- ChaosNode ----------------------------------------------------------------


def _chaos_server(art, engine="interp", **chaos_kw):
    inner = TMServer(CAP, engine=engine)
    inner.register("m", art)
    chaos_kw.setdefault("sleep", lambda d: None)
    return inner, ChaosNode(inner, **chaos_kw)


def _drive(chaos, x, n_ops):
    """A fixed op script; faults are swallowed, the schedule advances."""
    for i in range(n_ops):
        op = ("submit", "infer", "flush")[i % 3]
        try:
            if op == "submit":
                chaos.submit("m", x)
            elif op == "infer":
                chaos.infer("m", x)
            else:
                chaos.flush()
        except Exception:
            pass


def test_chaos_same_seed_replays_identical_fault_schedule():
    rng = np.random.default_rng(40)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    art = _program(model)
    x = rng.integers(0, 2, (3, 32)).astype(np.uint8)
    rates = dict(error_rate=0.2, latency_rate=0.15, latency_s=0.0,
                 overload_rate=0.15, hang_rate=0.1)
    logs = []
    for seed in (7, 7, 8):
        _, chaos = _chaos_server(art, seed=seed, **rates)
        _drive(chaos, x, 40)
        logs.append(list(chaos.fault_log))
    assert logs[0] == logs[1]        # same seed -> identical schedule
    assert logs[0] != logs[2]        # different seed -> different storm
    faults = {f for _, _, f in logs[0]}
    assert faults - {"ok"}           # the storm actually injected faults


def test_chaos_hung_handle_resolved_by_kill_then_revive():
    rng = np.random.default_rng(41)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    art = _program(model)
    inner, chaos = _chaos_server(art, name="h", seed=3, hang_rate=1.0)
    x = rng.integers(0, 2, (4, 32)).astype(np.uint8)
    h = chaos.submit("m", x)
    with pytest.raises(TimeoutError):
        h.wait(timeout=0.05)  # hung: the node accepted, then went silent
    assert h.status == "pending"
    chaos.kill()
    assert h.failed and h.status == "failed"
    with pytest.raises(NodeDown):
        h.result()
    with pytest.raises(NodeDown):
        chaos.submit("m", x)
    with pytest.raises(NodeDown):
        chaos.queue_depth()
    assert chaos.down and not chaos.scheduler_running
    chaos.revive()
    chaos.rates["hang"] = 0.0
    h2 = chaos.submit("m", x)
    chaos.flush()
    assert (h2.result() == _oracle_sums(cfg, acts, x).argmax(1)).all()


def test_chaos_corrupted_artifact_rejected_by_crc():
    """A bit-flipped TMProgram on the wire NEVER reaches a live
    accelerator: the CRC-32 integrity check rejects it on install."""
    rng = np.random.default_rng(42)
    _, _, model = _random_model(rng, 4, 10, 32)
    art = _program(model)
    inner = TMServer(CAP)
    chaos = ChaosNode(inner, seed=0, corrupt_rate=1.0)
    with pytest.raises(ValueError, match="checksum mismatch"):
        chaos.register("m", art)
    assert "m" not in inner.slots()  # the registry was never touched


def test_chaos_down_after_ops_is_deterministic():
    rng = np.random.default_rng(43)
    _, _, model = _random_model(rng, 4, 10, 32)
    art = _program(model)
    x = rng.integers(0, 2, (2, 32)).astype(np.uint8)
    _, chaos = _chaos_server(art, seed=0, down_after_ops=3)
    chaos.submit("m", x)
    chaos.submit("m", x)
    chaos.flush()  # op 3: the last one served
    with pytest.raises(NodeDown):
        chaos.submit("m", x)
    assert chaos.fault_log[-1] == (4, "submit", "down")


# -- routing under faults -----------------------------------------------------


def test_router_failover_bit_exact_across_heterogeneous_engines():
    """A failed-over request returns predictions AND class sums
    identical to the dense oracle even when the healthy replica runs a
    different engine than the one that failed."""
    rng = np.random.default_rng(50)
    cfg, acts, model = _random_model(rng, 5, 12, 40)
    art = _program(model)
    flaky_inner, flaky = _chaos_server(art, engine="interp",
                                       name="flaky", seed=5, error_rate=1.0)
    ok = TMServer(CAP, engine="popcount")
    ok.register("m", art)
    pool = FleetPool({"flaky": flaky, "ok": ok})
    health = FleetHealth(pool=pool, consecutive_failures=3,
                         probe_after_s=1e6)
    router = Router(pool, health=health,
                    retry=RetryPolicy(sleep=lambda d: None))
    handles = []
    for _ in range(3):
        x = rng.integers(0, 2, (6, 40)).astype(np.uint8)
        h = router.submit("m", x)
        assert h.routed_to == "ok"
        handles.append((h, x))
    assert health.state("flaky") == "quarantined"
    # the breaker event was mirrored into the node's own metrics
    assert flaky_inner.metrics.quarantines == 1
    assert ok.metrics.failovers == 3
    ok.flush()
    for h, x in handles:
        want = _oracle_sums(cfg, acts, x)
        assert (h.result() == want.argmax(1)).all()
        assert np.array_equal(np.asarray(h.class_sums), want)


class _FailsOnce(TMServer):
    """First submit (sync or async) raises; every later one serves."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.failures_left = 1

    def _maybe_fail(self):
        if self.failures_left > 0:
            self.failures_left -= 1
            raise RuntimeError("transient")

    def submit(self, slot, x, **kw):
        self._maybe_fail()
        return super().submit(slot, x, **kw)

    async def async_submit(self, slot, x, **kw):
        self._maybe_fail()
        return await super().async_submit(slot, x, **kw)


def test_router_retry_after_backoff_serves_bit_exact():
    """A single-node fleet whose node fails once: the router backs off,
    re-sweeps, and the RETRIED request is served bit-exact; the node's
    metrics record the retry."""
    rng = np.random.default_rng(51)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    node = _FailsOnce(CAP, engine="plan")
    node.register("m", _program(model))
    pool = FleetPool({"only": node})
    ft = _FakeTime()
    health = FleetHealth(pool=pool, consecutive_failures=5, clock=ft.clock)
    retry = RetryPolicy(max_attempts=3, backoff_base_s=0.01,
                        sleep=ft.sleep, clock=ft.clock)
    router = Router(pool, health=health, retry=retry)
    x = rng.integers(0, 2, (5, 32)).astype(np.uint8)
    h = router.submit("m", x)
    assert h.routed_to == "only"
    assert ft.sleeps == [(0.0, 0.01)]  # exactly one backoff sweep
    assert node.metrics.retries == 1
    node.flush()
    want = _oracle_sums(cfg, acts, x)
    assert (h.result() == want.argmax(1)).all()
    assert np.array_equal(np.asarray(h.class_sums), want)


def test_router_async_retry_with_injected_sleep():
    import asyncio

    rng = np.random.default_rng(52)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    node = _FailsOnce(CAP, engine="interp")
    node.register("m", _program(model))
    pool = FleetPool({"only": node})
    ft = _FakeTime()
    health = FleetHealth(pool=pool, consecutive_failures=5, clock=ft.clock)
    retry = RetryPolicy(max_attempts=3, backoff_base_s=0.02,
                        sleep=ft.sleep, clock=ft.clock)
    router = Router(pool, health=health, retry=retry)
    x = rng.integers(0, 2, (5, 32)).astype(np.uint8)
    h = asyncio.run(router.async_submit("m", x))
    assert h.routed_to == "only"
    assert ft.sleeps == [(0.0, 0.02)]  # injected sleep, not asyncio's
    node.flush()
    assert (h.result() == _oracle_sums(cfg, acts, x).argmax(1)).all()


def test_router_routes_around_dead_node_and_quarantines_it():
    """A node that dies outright (introspection raises NodeDown) is
    skipped by candidates, recorded as failing, and quarantined."""
    rng = np.random.default_rng(53)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    art = _program(model)
    _, dead = _chaos_server(art, engine="interp", name="d", seed=0)
    ok = TMServer(CAP, engine="plan")
    ok.register("m", art)
    pool = FleetPool({"d": dead, "ok": ok})
    health = FleetHealth(pool=pool, consecutive_failures=3,
                         probe_after_s=1e6)
    router = Router(pool, health=health,
                    retry=RetryPolicy(sleep=lambda d: None))
    dead.kill()
    x = rng.integers(0, 2, (4, 32)).astype(np.uint8)
    for _ in range(3):
        assert router.submit("m", x).routed_to == "ok"
    assert health.state("d") == "quarantined"


# -- structured engine faults -------------------------------------------------


def test_scheduler_engine_fault_fails_handles_and_loop_survives():
    """A raising batch body fails its requests with EngineFault (slot +
    cause) instead of stranding them; the slot serves again once the
    engine recovers."""
    rng = np.random.default_rng(60)
    cfg, acts, model = _random_model(rng, 4, 10, 32)
    server = TMServer(CAP, engine="plan")
    server.register("m", model)
    x = rng.integers(0, 2, (6, 32)).astype(np.uint8)
    h = server.submit("m", x)
    real = server.executor

    class _Boom:
        def __getattr__(self, name):
            return getattr(real, name)  # staging etc. still work

        def class_sums(self, prog, xx):
            raise RuntimeError("device fell off the bus")

    server.executor = _Boom()
    server.flush()  # must not raise: the batch body absorbs the fault
    assert h.failed and h.status == "failed"
    with pytest.raises(EngineFault) as ei:
        h.result()
    assert ei.value.slot == "m"
    assert isinstance(ei.value.cause, RuntimeError)
    assert "device fell off the bus" in str(ei.value)
    # recovery: the same server keeps serving after the engine heals
    server.executor = real
    h2 = server.submit("m", x)
    server.flush()
    assert (h2.result() == _oracle_sums(cfg, acts, x).argmax(1)).all()


# -- failure-aware rollouts ---------------------------------------------------


def _three_node_pool(v1, victim_kw):
    """n0/n2 plain, n1 chaos-wrapped (the wave stage's only member)."""
    inners = {}
    for i, eng in enumerate(("interp", "plan", "popcount")):
        inner = TMServer(CAP, engine=eng)
        inner.register("m", v1)
        inners[f"n{i}"] = inner
    victim = ChaosNode(inners["n1"], name="n1", sleep=lambda d: None,
                       **victim_kw)
    pool = FleetPool({"n0": inners["n0"], "n1": victim, "n2": inners["n2"]})
    return inners, victim, pool


def test_rollout_midwave_node_death_quarantines_and_rolls_back_reachable():
    """A node dying mid-wave is a gate failure: the rollback completes
    on every reachable node, the corpse is quarantined and recorded
    unreachable (it keeps the attempted artifact until it returns)."""
    rng = np.random.default_rng(70)
    _, _, m1 = _random_model(rng, 5, 12, 40)
    _, _, m2 = _random_model(rng, 5, 12, 40)
    v1, v2 = _program(m1), _program(m2)
    # op 1 = the wave install (survives), op 2 = the gate submit (dies)
    inners, victim, pool = _three_node_pool(v1, dict(seed=0,
                                                     down_after_ops=1))
    health = FleetHealth(pool=pool)
    X = rng.integers(0, 2, (24, 40)).astype(np.uint8)
    with pytest.raises(RolloutAborted) as ei:
        RolloutManager(pool, health=health).rollout("m", v2, holdout_x=X)
    err = ei.value
    assert err.stage == "wave" and "died during the gate" in err.reason
    assert err.report.rolled_back == ("n0",)
    assert err.report.unreachable == ("n1",)
    # reachable nodes are back on (or never left) the OLD checksum
    assert inners["n0"].installed_checksum("m") == v1.checksum
    assert inners["n0"].registry.get("m").provenance.startswith("rollback:")
    assert inners["n2"].installed_checksum("m") == v1.checksum
    assert "rollout" not in inners["n2"].registry.get("m").provenance
    # the corpse kept the attempted artifact and is quarantined
    assert inners["n1"].installed_checksum("m") == v2.checksum
    assert health.state("n1") == "quarantined"


def test_rollout_corrupt_install_aborts_cleanly_and_quarantines():
    """Corrupted wire bytes die at the node's CRC check BEFORE its
    registry is touched: the stage aborts, the victim still runs the
    old program, the canary is rolled back."""
    rng = np.random.default_rng(71)
    _, _, m1 = _random_model(rng, 5, 12, 40)
    _, _, m2 = _random_model(rng, 5, 12, 40)
    v1, v2 = _program(m1), _program(m2)
    inners, victim, pool = _three_node_pool(v1, dict(seed=0,
                                                     corrupt_rate=1.0))
    health = FleetHealth(pool=pool)
    X = rng.integers(0, 2, (24, 40)).astype(np.uint8)
    with pytest.raises(RolloutAborted) as ei:
        RolloutManager(pool, health=health).rollout("m", v2, holdout_x=X)
    err = ei.value
    assert err.stage == "wave" and "failed install" in err.reason
    assert "checksum mismatch" in err.reason
    assert err.report.rolled_back == ("n0",)
    assert err.report.unreachable == ()  # alive, just fed garbage
    for name in ("n0", "n1", "n2"):
        assert inners[name].installed_checksum("m") == v1.checksum
    assert health.state("n1") == "quarantined"


# -- dead-node-tolerant pool lifecycle ----------------------------------------


def test_pool_remove_and_stop_all_tolerate_dead_nodes():
    rng = np.random.default_rng(80)
    _, _, model = _random_model(rng, 4, 10, 32)
    art = _program(model)
    inner, dead = _chaos_server(art, name="dead", seed=0)
    ok = TMServer(CAP, engine="plan")
    ok.register("m", art)
    pool = FleetPool({"dead": dead, "ok": ok})
    pool.start_all()
    try:
        dead.kill()
        # rollups flag the corpse instead of raising
        ms = pool.metrics_summary()
        assert ms["unreachable"] == ["dead"] and "ok" in ms["nodes"]
        assert pool.queue_depths() == {"ok": 0}
        assert [n for n, _ in pool.nodes_with_slot("m")] == ["ok"]
        # teardown completes; the failure is a recorded warning
        pool.stop_all()
        assert any("dead" in w for w in pool.warnings)
        n_warnings = len(pool.warnings)
        assert pool.remove("dead") is dead
        assert "dead" not in pool
        assert len(pool.warnings) == n_warnings + 1
    finally:
        pool.stop_all()
        inner.stop()  # the killed wrapper cannot stop its server's loop


# -- deprecations -------------------------------------------------------------


def test_gate_timeout_constant_deprecation_fires_once():
    """Reading the deprecated fleet.rollout.GATE_TIMEOUT_S constant
    warns exactly once per process; importing the module stays silent."""
    code = textwrap.dedent(
        """
        import warnings

        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            import repro.fleet.rollout as ro      # import: silent
            v1 = ro.GATE_TIMEOUT_S                # first access: warns
            v2 = ro.GATE_TIMEOUT_S                # cached: silent
        assert v1 == v2 == 120.0
        dep = [
            w for w in rec
            if issubclass(w.category, DeprecationWarning)
            and "GATE_TIMEOUT_S" in str(w.message)
        ]
        assert len(dep) == 1, [str(w.message) for w in rec]
        assert "gate_timeout_s" in str(dep[0].message)
        print("GATE-OK")
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
    assert "GATE-OK" in out.stdout
