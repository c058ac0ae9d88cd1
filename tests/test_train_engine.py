"""TrainEngine plugin API + fused packed-TA kernel tests.

The load-bearing guarantee: every registered train engine ('reference'
host path, 'packed' fused int8 kernel, 'sharded' dist-mesh step) produces
the BIT-IDENTICAL canonical TA state for the same (key, step, batch) —
backend choice is a speed knob, never a semantics knob.  Checked both
directly (fixed seeds, adversarial shapes) and as a hypothesis property
(random shapes/keys/step offsets), plus checkpoint-resume across
backends, the structured capacity envelope, registry/selection behavior,
and the legacy RecalWorker construction shim.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.accel.capacity import CapacityExceeded, CapacityPlan
from repro.core.tm import TMConfig, init_state
from repro.core.train import fit_step
from repro.dist.sharding import make_mesh
from repro.kernels.tm_train import (
    MAX_PACKED_STATES,
    check_packable,
    fused_fit_step,
    fused_train_batch,
    fused_train_batch_ref,
    pack_ta_state,
    supports_packed_states,
    unpack_ta_state,
)
from repro.recal import (
    TRAIN_ENGINES,
    RecalWorker,
    TrainEngine,
    TrainEngineBase,
    make_train_engine,
    register_train_engine,
    select_train_engine,
    train_engine_names,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


def _batch(rng, B, F, M):
    x = rng.integers(0, 2, (B, F)).astype(np.uint8)
    y = rng.integers(0, M, B).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def _all_engines(cfg, *, plan=None):
    """One instance of every registered engine for cfg (sharded on a 1x1
    mesh so it runs in a single-device test process)."""
    return {
        "reference": make_train_engine("reference", cfg, plan=plan),
        "packed": make_train_engine("packed", cfg, plan=plan),
        "sharded": make_train_engine("sharded", cfg, mesh=_mesh11(), plan=plan),
    }


def _run_engine(engine, cfg, state0, key, batches, *, step0=0):
    """Drive `engine` through `batches` starting at step0; return the
    canonical final state."""
    internal = engine.prepare(state0)
    for j, (xb, yb) in enumerate(batches):
        internal = engine.fit_step(internal, key, xb, yb, step=step0 + j)
    return np.asarray(engine.canonical(internal))


# ---------------------------------------------------------------------------
# packed representation
# ---------------------------------------------------------------------------


def test_pack_unpack_roundtrip_and_action_boundary():
    cfg = TMConfig(n_classes=3, n_clauses=10, n_features=8)
    key = jax.random.key(0)
    state = init_state(cfg, key)
    packed = pack_ta_state(cfg, state)
    assert packed.dtype == jnp.int8
    assert packed.shape == (3, 10, 8, 2)
    back = unpack_ta_state(cfg, packed)
    assert back.dtype == jnp.int32
    assert jnp.array_equal(back, state)
    # include action (state > N) survives the centered remap exactly
    from repro.kernels.tm_train import packed_include_actions

    acts = packed_include_actions(packed.reshape(3, 10, 16))
    assert jnp.array_equal(acts, state > cfg.n_states)
    # extremes of the legal state range fit int8 exactly
    lo = jnp.full_like(state, 1)
    hi = jnp.full_like(state, 2 * cfg.n_states)
    assert jnp.array_equal(unpack_ta_state(cfg, pack_ta_state(cfg, lo)), lo)
    assert jnp.array_equal(unpack_ta_state(cfg, pack_ta_state(cfg, hi)), hi)


def test_packable_gate():
    ok = TMConfig(n_classes=2, n_clauses=4, n_features=4,
                  n_states=MAX_PACKED_STATES)
    too_big = TMConfig(n_classes=2, n_clauses=4, n_features=4,
                       n_states=MAX_PACKED_STATES + 1)
    assert supports_packed_states(ok)
    assert not supports_packed_states(too_big)
    check_packable(ok)
    with pytest.raises(ValueError, match="reference"):
        check_packable(too_big)
    with pytest.raises(ValueError, match="reference"):
        make_train_engine("packed", too_big)


# ---------------------------------------------------------------------------
# bit-identity: packed == reference == sharded (the acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "M,C,F,B",
    [
        (2, 6, 5, 16),    # tiny
        (3, 40, 11, 33),  # C > 32 (bitplane chunking), ragged batch
        (5, 10, 16, 7),   # ragged sub-word batch
    ],
)
def test_fused_kernel_bit_identical_to_fit_step(M, C, F, B):
    """fused_fit_step == core.train.fit_step(parallel=True), bit for bit,
    including across multiple steps (state feeds back through int8)."""
    cfg = TMConfig(n_classes=M, n_clauses=C, n_features=F)
    key = jax.random.key(17)
    rng = np.random.default_rng(23)
    state = init_state(cfg, jax.random.key(1))
    packed = pack_ta_state(cfg, state)
    for step in (0, 1, 7):
        xb, yb = _batch(rng, B, F, M)
        state = fit_step(cfg, state, key, xb, yb, step=step, parallel=True)
        packed = fused_fit_step(cfg, packed, key, xb, yb, step=step)
    assert jnp.array_equal(unpack_ta_state(cfg, packed), state)


def test_fused_kernel_all_excluded_clauses():
    """All-TA-states-at-minimum => every clause all-excluded => training
    clause output 1 everywhere; the packed AND-identity path must agree
    with the dense oracle from the first update."""
    cfg = TMConfig(n_classes=3, n_clauses=12, n_features=9)
    state = jnp.ones((3, 12, 18), jnp.int32)  # everything excluded
    key = jax.random.key(3)
    rng = np.random.default_rng(5)
    xb, yb = _batch(rng, 20, 9, 3)
    packed = pack_ta_state(cfg, state)
    ref = fit_step(cfg, state, key, xb, yb, step=0, parallel=True)
    out = fused_fit_step(cfg, packed, key, xb, yb, step=0)
    assert jnp.array_equal(unpack_ta_state(cfg, out), ref)


def test_fused_kernel_matches_independent_oracle():
    """fused_train_batch vs the deliberately-naive unpack->reference->
    repack oracle (two independently-structured computations)."""
    cfg = TMConfig(n_classes=4, n_clauses=24, n_features=12)
    key = jax.random.fold_in(jax.random.key(9), 4)
    rng = np.random.default_rng(11)
    xb, yb = _batch(rng, 40, 12, 4)
    packed = pack_ta_state(cfg, init_state(cfg, jax.random.key(2)))
    out = fused_train_batch(cfg, packed.copy(), key, xb, yb)
    ref = fused_train_batch_ref(cfg, packed.copy(), key, xb, yb)
    assert jnp.array_equal(out, ref)


def test_all_engines_bit_identical_multi_step():
    """The tentpole guarantee at the engine level: reference, packed and
    sharded produce the same canonical state over a multi-step run with
    a ragged tail batch and a nonzero step offset."""
    cfg = TMConfig(n_classes=3, n_clauses=34, n_features=10)
    key = jax.random.key(29)
    rng = np.random.default_rng(31)
    state0 = init_state(cfg, jax.random.key(4))
    batches = [_batch(rng, b, 10, 3) for b in (32, 32, 13)]
    finals = {
        name: _run_engine(e, cfg, state0, key, batches, step0=5)
        for name, e in _all_engines(cfg).items()
    }
    assert np.array_equal(finals["reference"], finals["packed"])
    assert np.array_equal(finals["reference"], finals["sharded"])


def test_checkpoint_resume_across_engines():
    """A (key, step, state) checkpoint taken mid-run on one engine resumes
    bit-exactly on ANY other engine: 2 steps on packed + 2 on sharded ==
    4 straight reference steps."""
    cfg = TMConfig(n_classes=4, n_clauses=20, n_features=8)
    key = jax.random.key(41)
    rng = np.random.default_rng(43)
    state0 = init_state(cfg, jax.random.key(6))
    batches = [_batch(rng, 24, 8, 4) for _ in range(4)]
    eng = _all_engines(cfg)

    straight = _run_engine(eng["reference"], cfg, state0, key, batches)
    mid = _run_engine(eng["packed"], cfg, state0, key, batches[:2])
    hopped = _run_engine(eng["sharded"], cfg, mid, key, batches[2:], step0=2)
    assert np.array_equal(straight, hopped)


def test_engine_equivalence_property():
    """Hypothesis property: over random shapes, keys, step offsets and
    batch sizes (incl. sub-word ragged), packed == reference == sharded
    final canonical states bit-exactly."""
    pytest.importorskip("hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    shapes = st.tuples(
        st.integers(2, 5),     # classes
        st.integers(2, 40),    # clauses (crosses the 32 bitplane boundary)
        st.integers(2, 12),    # raw features
        st.integers(1, 40),    # batch rows (crosses the 32 word boundary)
        st.integers(0, 2**16), # seed
        st.integers(0, 2**20), # step offset
        st.booleans(),         # start from all-excluded state
    )

    @given(shapes)
    @settings(max_examples=25, deadline=None)
    def check(spec):
        M, C, F, B, seed, step0, all_excl = spec
        cfg = TMConfig(n_classes=M, n_clauses=C, n_features=F)
        key = jax.random.key(seed)
        rng = np.random.default_rng(seed)
        if all_excl:
            state0 = jnp.ones((M, C, 2 * F), jnp.int32)
        else:
            state0 = init_state(cfg, jax.random.key(seed + 1))
        batches = [_batch(rng, B, F, M), _batch(rng, max(1, B - 3), F, M)]
        finals = {
            name: _run_engine(e, cfg, state0, key, batches, step0=step0)
            for name, e in _all_engines(cfg).items()
        }
        assert np.array_equal(finals["reference"], finals["packed"])
        assert np.array_equal(finals["reference"], finals["sharded"])

    check()


# ---------------------------------------------------------------------------
# registry / selection / construction
# ---------------------------------------------------------------------------


def test_registry_contents_and_protocol():
    assert train_engine_names() == ["packed", "reference", "sharded"]
    cfg = TMConfig(n_classes=2, n_clauses=4, n_features=4)
    for name in ("reference", "packed"):
        e = make_train_engine(name, cfg)
        assert isinstance(e, TrainEngine)  # runtime-checkable protocol
        assert e.name == name
    assert TRAIN_ENGINES["sharded"].needs_mesh
    assert not TRAIN_ENGINES["packed"].needs_mesh


def test_register_conflict_raises():
    with pytest.raises(ValueError, match="already registered"):

        @register_train_engine("packed")
        class Impostor(TrainEngineBase):
            pass

    assert TRAIN_ENGINES["packed"].__name__ == "PackedTrainEngine"


def test_select_train_engine_rules():
    small = TMConfig(n_classes=2, n_clauses=4, n_features=4)
    big = TMConfig(n_classes=2, n_clauses=4, n_features=4,
                   n_states=MAX_PACKED_STATES + 8)
    # fastest mesh-free engine wins; packed bows out past its state range
    assert select_train_engine(small) == "packed"
    assert select_train_engine(big) == "reference"
    assert select_train_engine() == "packed"  # no cfg: no supports() veto
    # a mesh selects the mesh-consuming engine
    assert select_train_engine(small, mesh=_mesh11()) == "sharded"


def test_make_train_engine_errors_and_passthrough():
    cfg = TMConfig(n_classes=2, n_clauses=4, n_features=4)
    with pytest.raises(ValueError, match="unknown train engine"):
        make_train_engine("warp", cfg)
    ref = make_train_engine("reference", cfg)
    assert make_train_engine(ref, cfg) is ref
    # mesh is only forwarded to engines that declare needs_mesh
    assert make_train_engine("reference", cfg, mesh=_mesh11()).name == "reference"


# ---------------------------------------------------------------------------
# capacity envelope (structured errors, not bare asserts)
# ---------------------------------------------------------------------------


def test_fit_step_capacity_exceeded():
    cfg = TMConfig(n_classes=2, n_clauses=4, n_features=4)
    plan = CapacityPlan(batch_words=1)  # 32-row envelope
    state = init_state(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    xb, yb = _batch(rng, 33, 4, 2)
    with pytest.raises(CapacityExceeded) as ei:
        fit_step(cfg, state, jax.random.key(1), xb, yb, step=0,
                 parallel=True, plan=plan)
    err = ei.value
    assert isinstance(err, ValueError)
    assert err.knob == "batch_words"
    assert err.required == 2 and err.capacity == 1
    # within the envelope: fine
    fit_step(cfg, state, jax.random.key(1), xb[:32], yb[:32], step=0,
             parallel=True, plan=plan)


def test_fused_and_engine_capacity_exceeded():
    cfg = TMConfig(n_classes=2, n_clauses=4, n_features=4)
    plan = CapacityPlan(batch_words=1)
    rng = np.random.default_rng(1)
    xb, yb = _batch(rng, 40, 4, 2)
    packed = pack_ta_state(cfg, init_state(cfg, jax.random.key(0)))
    with pytest.raises(CapacityExceeded):
        fused_fit_step(cfg, packed, jax.random.key(1), xb, yb, step=0,
                       plan=plan)
    for name, e in _all_engines(cfg, plan=plan).items():
        internal = e.prepare(init_state(cfg, jax.random.key(0)))
        with pytest.raises(CapacityExceeded):
            e.fit_step(internal, jax.random.key(1), xb, yb, step=0)


def test_worker_respects_plan():
    cfg = TMConfig(n_classes=2, n_clauses=4, n_features=4)
    worker = RecalWorker(cfg, key=jax.random.key(0),
                         plan=CapacityPlan(batch_words=1))
    rng = np.random.default_rng(2)
    x = rng.integers(0, 2, (64, 4)).astype(np.uint8)
    y = rng.integers(0, 2, 64).astype(np.int32)
    with pytest.raises(CapacityExceeded):
        worker.fine_tune(x, y)
    assert worker.step_count == 0  # failed batches consume no step ids
    worker.fine_tune(x[:32], y[:32])
    assert worker.step_count == 1


# ---------------------------------------------------------------------------
# RecalWorker over the engine API
# ---------------------------------------------------------------------------


def test_worker_engine_parity_and_state_boundary():
    """Workers on different engines stay bit-identical through the epoch
    loop (shared shuffle stream), and the canonical-state boundary
    (state property / snapshot / restore) hides the int8 representation."""
    cfg = TMConfig(n_classes=3, n_clauses=18, n_features=8)
    rng = np.random.default_rng(7)
    x = rng.integers(0, 2, (300, 8)).astype(np.uint8)
    y = rng.integers(0, 3, 300).astype(np.int32)
    wp = RecalWorker(cfg, key=jax.random.key(1))  # auto -> packed
    wr = RecalWorker(cfg, key=jax.random.key(1), train_engine="reference")
    assert wp.train_engine == "packed" and wr.train_engine == "reference"
    assert wp._internal.dtype == jnp.int8  # fused representation persists
    assert wp.state.dtype == jnp.int32    # ...but the boundary is canonical
    wp.fine_tune_epochs(x, y, epochs=2, batch=64)
    wr.fine_tune_epochs(x, y, epochs=2, batch=64)
    assert np.array_equal(wp.snapshot(), wr.snapshot())
    # restore() round-trips through prepare(); subclasses may assign state
    snap = wr.snapshot()
    wp.fine_tune(x[:64], y[:64])
    wp.restore(snap)
    assert np.array_equal(wp.snapshot(), snap)
    wp.state = init_state(cfg, jax.random.key(9))
    assert np.array_equal(wp.snapshot(), np.asarray(init_state(cfg, jax.random.key(9))))


def test_worker_legacy_sharded_shim():
    """Satellite: the pre-engine RecalWorker(mesh=, sharded_batch=)
    construction still works (maps to the 'sharded' engine) but warns
    exactly once per process — checked in a subprocess so this test is
    immune to warning state from the rest of the suite."""
    code = textwrap.dedent(
        """
        import warnings
        import jax
        import numpy as np
        from repro.core.tm import TMConfig
        from repro.dist.sharding import make_mesh
        from repro.recal import RecalWorker

        cfg = TMConfig(n_classes=2, n_clauses=6, n_features=4)
        mesh = make_mesh((1, 1), ("data", "model"))
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            w1 = RecalWorker(cfg, key=jax.random.key(0), mesh=mesh,
                             sharded_batch=16)
            w2 = RecalWorker(cfg, key=jax.random.key(0), mesh=mesh,
                             sharded_batch=16)
        dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
        assert len(dep) == 1, [str(w.message) for w in rec]
        assert "train_engine='sharded'" in str(dep[0].message)
        assert w1.train_engine == "sharded"

        # the shimmed worker still trains bit-identically to reference
        wr = RecalWorker(cfg, key=jax.random.key(0),
                         train_engine="reference")
        rng = np.random.default_rng(0)
        x = rng.integers(0, 2, (16, 4)).astype(np.uint8)
        y = rng.integers(0, 2, 16).astype(np.int32)
        w1.fine_tune(x, y)
        wr.fine_tune(x, y)
        assert np.array_equal(w1.snapshot(), wr.snapshot())

        # new-style construction is silent
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            RecalWorker(cfg, key=jax.random.key(0))
        assert not [
            w for w in rec if issubclass(w.category, DeprecationWarning)
        ]
        print("WORKER-SHIM-OK")
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
    assert "WORKER-SHIM-OK" in out.stdout
