"""Checkpointing, fault tolerance, elastic resharding, data-stream resume."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import TokenStream, TokenStreamConfig
from repro.dist.sharding import make_mesh
from repro.runtime_ft.supervisor import (
    HeartbeatTracker,
    StragglerMonitor,
    run_with_restarts,
)


def _state():
    return {
        "w": jnp.arange(12.0).reshape(3, 4),
        "opt": {"m": jnp.zeros((3, 4)), "step": jnp.int32(0)},
    }


def test_save_restore_roundtrip(tmp_path):
    ckpt = CheckpointManager(tmp_path)
    st = _state()
    ckpt.save(5, st)
    out = ckpt.restore(5, like=_state())
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        assert jnp.array_equal(a, b)


def test_atomicity_no_tmp_left(tmp_path):
    ckpt = CheckpointManager(tmp_path)
    ckpt.save(1, _state())
    assert not list(tmp_path.glob("*.tmp"))
    assert ckpt.latest_step() == 1


def test_gc_keeps_last(tmp_path):
    ckpt = CheckpointManager(tmp_path, keep_last=2)
    for s in (1, 2, 3, 4):
        ckpt.save(s, _state())
    assert ckpt.steps() == [3, 4]


def test_restart_recovers_and_completes(tmp_path):
    """Inject a crash at step 17; the supervisor restores from step 10 and
    completes all 30 steps with exactly-once semantics on the counter."""
    ckpt = CheckpointManager(tmp_path)
    crashed = {"done": False}

    def make_state():
        return {"count": jnp.int32(0)}

    def step_fn(state, step):
        return {"count": state["count"] + 1}

    def fault(step):
        if step == 17 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("simulated node failure")

    state, stats = run_with_restarts(
        total_steps=30, make_state=make_state, step_fn=step_fn,
        ckpt=ckpt, save_every=10, fault_injector=fault,
    )
    assert stats.restarts == 1
    assert stats.restored_from == 10
    assert int(state["count"]) == 30


def test_stream_exact_resume():
    cfg = TokenStreamConfig(vocab=1000, seq_len=16, global_batch=4, seed=3)
    s1 = TokenStream(cfg)
    batches = [s1.next_batch()["tokens"] for _ in range(5)]
    saved = None
    s2 = TokenStream(cfg)
    for i in range(3):
        s2.next_batch()
    saved = s2.state()
    s3 = TokenStream(cfg)
    s3.restore(saved)
    assert np.array_equal(s3.next_batch()["tokens"], batches[3])


def test_straggler_detection():
    mon = StragglerMonitor(deadline_factor=2.0, max_strikes=2)
    for _ in range(10):
        assert mon.observe("h0", 1.0) == "ok"
    assert mon.observe("h1", 5.0) == "suspect"
    assert mon.observe("h1", 5.0) == "evict"
    # healthy host clears strikes
    mon.observe("h2", 5.0)
    assert mon.observe("h2", 1.0) == "ok"
    assert "h2" not in mon.strikes


def test_heartbeat_dead_host():
    t = {"now": 0.0}
    hb = HeartbeatTracker(timeout_s=10, clock=lambda: t["now"])
    hb.beat("a")
    hb.beat("b")
    t["now"] = 5.0
    hb.beat("a")
    t["now"] = 12.0
    assert hb.dead_hosts() == ["b"]


def test_elastic_reshard(tmp_path):
    """Checkpoint written under one mesh restores onto another (smaller)."""
    from repro.runtime_ft.elastic import plan_new_mesh

    assert plan_new_mesh(512, model_parallel=16) == (32, 16)
    assert plan_new_mesh(496, model_parallel=16) == (31, 16)  # lost one host
    with pytest.raises(ValueError):
        plan_new_mesh(8, model_parallel=16)

    ckpt = CheckpointManager(tmp_path)
    st = _state()
    ckpt.save(1, st)
    mesh = make_mesh((1, 1), ("data", "model"))
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), st)
    out = ckpt.restore(1, like=st, shardings=sh)
    assert jnp.array_equal(out["w"], st["w"])


def test_async_save(tmp_path):
    ckpt = CheckpointManager(tmp_path)
    t = ckpt.save_async(7, _state())
    t.join()
    out = ckpt.restore(7, like=_state())
    assert jnp.array_equal(out["w"], _state()["w"])
    assert not list(tmp_path.glob("*.tmp"))
