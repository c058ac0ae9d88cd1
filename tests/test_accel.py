"""The repro.accel façade: capacity negotiation, the Engine plugin
protocol, and the serializable TMProgram artifact.

Covers the ISSUE-5 acceptance surface: TMProgram bytes round-trip with
bit-exact class sums on every engine, CapacityPlan.for_models minimality
and word-quantization, CapacityExceeded knob reporting, deterministic
engine auto-selection, and compile_cache_size()==1 across hot-swaps of
differently-sized models within one negotiated plan.
"""

import dataclasses
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from repro.accel import (
    ENGINES,
    Accelerator,
    CapacityExceeded,
    CapacityPlan,
    EngineBase,
    QUANTA,
    TMProgram,
    make_engine,
    model_requirements,
    register_engine,
    select_engine,
)
from repro.core import TMConfig, batch_class_sums, state_from_actions
from repro.core.compress import encode
from repro.serve_tm import ModelRegistry, TMServer

ENGINE_NAMES = ("interp", "plan", "sharded", "popcount")


def _random_model(rng, M, C, F, density=0.05):
    cfg = TMConfig(n_classes=M, n_clauses=C, n_features=F)
    acts = rng.random((M, C, 2 * F)) < density
    return cfg, acts, encode(cfg, acts)


def _oracle_sums(cfg, acts, X):
    return np.asarray(
        batch_class_sums(cfg, state_from_actions(cfg, acts), jnp.asarray(X))
    )


# ---------------------------------------------------------------------------
# CapacityPlan negotiation
# ---------------------------------------------------------------------------

def test_for_models_fits_population_and_is_quantized():
    rng = np.random.default_rng(0)
    models = [
        _random_model(rng, 5, 12, 40)[2],
        _random_model(rng, 9, 8, 72)[2],
        _random_model(rng, 3, 20, 24, density=0.15)[2],
    ]
    plan = CapacityPlan.for_models(models)
    for m in models:
        assert plan.fits(m), plan.violations(m)
    for knob, q in QUANTA.items():
        assert getattr(plan, knob) % q == 0, (knob, getattr(plan, knob))
    # the envelope is driven by the population maxima
    assert plan.class_capacity == 9
    assert plan.feature_capacity == 80  # 72 -> quantized to 16


def test_for_models_minimality_per_quantum():
    """Shrinking any model-derived knob by ONE quantum must evict some
    model from the envelope — the plan is minimal at the word grain."""
    rng = np.random.default_rng(1)
    models = [_random_model(rng, 6, 10, 48, density=0.1)[2],
              _random_model(rng, 4, 14, 64)[2]]
    plan = CapacityPlan.for_models(models)  # headroom=0
    for knob in CapacityPlan.KNOBS:
        if knob == "batch_words":  # traffic-shaped, not model-derived
            continue
        if getattr(plan, knob) - QUANTA[knob] < 1:
            continue  # already at the floor (e.g. weight_planes=1)
        shrunk = dataclasses.replace(
            plan, **{knob: getattr(plan, knob) - QUANTA[knob]}
        )
        assert any(not shrunk.fits(m) for m in models), knob


def test_for_models_headroom_and_errors():
    rng = np.random.default_rng(2)
    model = _random_model(rng, 4, 10, 32)[2]
    base = CapacityPlan.for_models([model])
    roomy = CapacityPlan.for_models([model], headroom=1.0)
    assert roomy.instruction_capacity >= 2 * model.n_instructions
    assert roomy.clause_capacity >= base.clause_capacity
    # task-pinned dims never inflate: classes/features are what they are
    assert roomy.class_capacity == base.class_capacity == 4
    assert roomy.feature_capacity == base.feature_capacity == 32
    assert roomy.batch_words == base.batch_words
    with pytest.raises(ValueError, match="at least one model"):
        CapacityPlan.for_models([])
    with pytest.raises(ValueError, match="headroom"):
        CapacityPlan.for_models([model], headroom=-0.5)
    with pytest.raises(ValueError, match="positive integer"):
        CapacityPlan(class_capacity=0)


def test_capacity_exceeded_reports_knob_and_required_value():
    rng = np.random.default_rng(3)
    _, _, small = _random_model(rng, 3, 6, 24)
    # generous everywhere except the knob under test, so the report is
    # unambiguous (validate reports violations in KNOBS order)
    plan = dataclasses.replace(
        CapacityPlan.for_models([small]),
        instruction_capacity=8192, clause_capacity=64, include_capacity=64,
    )
    _, _, wide = _random_model(rng, 3, 6, 120)
    with pytest.raises(CapacityExceeded) as ei:
        plan.validate(wide)
    err = ei.value
    assert isinstance(err, ValueError)  # legacy guards keep working
    assert err.knob == "feature_capacity"
    assert err.required == 120
    assert err.capacity == plan.feature_capacity
    assert "feature_capacity" in str(err)
    # widen_to is the advertised remedy
    widened = plan.widen_to(wide)
    assert widened.fits(wide) and widened.fits(small)
    assert widened.feature_capacity == 128  # 120 quantized up to 16s

    _, _, classy = _random_model(rng, 14, 6, 24)
    with pytest.raises(CapacityExceeded) as ei:
        plan.validate(classy)
    assert ei.value.knob == "class_capacity"
    assert ei.value.required == 14
    # knob subsets: an engine that has no class bank wouldn't trip it
    assert plan.fits(classy, knobs=("feature_capacity",))


def test_model_requirements_extents():
    rng = np.random.default_rng(4)
    cfg, acts, model = _random_model(rng, 5, 12, 40, density=0.1)
    req = model_requirements(model)
    assert req["instruction_capacity"] == model.n_instructions
    assert req["class_capacity"] == 5
    assert req["feature_capacity"] == 40
    # clause/include extents match the dense action mask
    per_class = (acts.any(axis=2)).sum(axis=1).max()
    assert req["clause_capacity"] == per_class
    assert req["include_capacity"] == acts.sum(axis=2).max()


# ---------------------------------------------------------------------------
# TMProgram artifact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINE_NAMES)
def test_tmprogram_bytes_roundtrip_bit_exact(engine):
    """compile -> to_bytes -> from_bytes -> load must reproduce class
    sums bit-exactly on every engine (the acceptance criterion)."""
    rng = np.random.default_rng(5)
    cfg, acts, model = _random_model(rng, 5, 12, 40)
    acc = Accelerator.for_models([model], engine=engine, batch_words=2)
    art = acc.compile(model)
    blob = art.to_bytes()
    art2 = TMProgram.from_bytes(blob)
    assert art2 == art
    assert art2.checksum == art.checksum
    assert art2.capacity == acc.plan
    acc.load("m", blob, provenance="wire")
    X = rng.integers(0, 2, (33, 40)).astype(np.uint8)
    assert (acc.class_sums("m", X) == _oracle_sums(cfg, acts, X)).all()
    assert acc.compile_cache_size() == 1
    entry = acc.registry.get("m")
    assert entry.provenance == "wire"
    assert entry.artifact is not None
    assert entry.artifact.checksum == art.checksum


def test_tmprogram_rejects_corruption():
    rng = np.random.default_rng(6)
    _, _, model = _random_model(rng, 4, 8, 32)
    art = TMProgram(CapacityPlan.for_models([model]), model)
    blob = bytearray(art.to_bytes())
    with pytest.raises(ValueError, match="checksum"):
        TMProgram.from_bytes(bytes(blob[:-2] + bytes([blob[-2] ^ 0xFF, blob[-1]])))
    with pytest.raises(ValueError, match="truncated"):
        TMProgram.from_bytes(bytes(blob[:10]))
    with pytest.raises(ValueError, match="truncated"):
        TMProgram.from_bytes(bytes(blob[:-4]))
    with pytest.raises(ValueError, match="not a TMProgram"):
        TMProgram.from_bytes(b"NOPE" + bytes(blob[4:]))
    newer = bytearray(blob)
    newer[4:6] = (99).to_bytes(2, "little")
    with pytest.raises(ValueError, match="version"):
        TMProgram.from_bytes(bytes(newer))


def test_compile_gate_covers_the_load_path():
    """Anything compile() accepts must install on the same accelerator:
    the serving node's load path never discovers a capacity violation
    the training node's gate missed (the plan engine's clause-table
    bound is part of its validated knobs)."""
    rng = np.random.default_rng(12)
    plan = CapacityPlan(
        instruction_capacity=4096, feature_capacity=32, class_capacity=16,
        clause_capacity=8, include_capacity=8, batch_words=1,
    )
    acc = Accelerator(plan, engine="plan")
    # 16 classes x ~18 non-empty clauses blows the 16*8 segment table —
    # compile must say so; it must NOT surface only at load time
    cfg, acts, clausey = _random_model(rng, 16, 20, 16, density=0.08)
    with pytest.raises(CapacityExceeded) as ei:
        acc.compile(clausey)
    assert ei.value.knob == "clause_capacity"
    # and a compile-accepted model always loads
    cfg2, acts2, ok = _random_model(rng, 8, 6, 16, density=0.08)
    acc.load("m", acc.compile(ok).to_bytes())
    X = rng.integers(0, 2, (9, 16)).astype(np.uint8)
    assert (acc.class_sums("m", X) == _oracle_sums(cfg2, acts2, X)).all()


def test_instruction_metric_extend_heavy_stream():
    """plan/popcount operand vectors hold only the INCLUDES; boundary
    EXTEND words never materialize there.  An EXTEND-heavy stream (high
    literal slots) must load on those engines with instruction_capacity
    sized for the includes, while the interp engine (whose instruction
    memory holds the raw stream) reports the full stream depth."""
    cfg = TMConfig(n_classes=2, n_clauses=2, n_features=4096)
    acts = np.zeros((2, 2, 8192), bool)
    acts[:, :, 8190] = True  # offset 8190 needs two EXTENDs per include
    model = encode(cfg, acts)
    assert model.n_instructions == 12  # 4 includes + 8 EXTENDs
    plan = CapacityPlan(
        instruction_capacity=8, feature_capacity=4096, class_capacity=2,
        clause_capacity=2, include_capacity=1, batch_words=1,
    )
    rng = np.random.default_rng(13)
    X = rng.integers(0, 2, (5, 4096)).astype(np.uint8)
    oracle = _oracle_sums(cfg, acts, X)
    for name in ("plan", "popcount"):
        acc = Accelerator(plan, engine=name)
        acc.load("m", acc.compile(model))  # 4 includes <= 8: fits
        assert (acc.class_sums("m", X) == oracle).all()
    with pytest.raises(CapacityExceeded) as ei:
        Accelerator(plan, engine="interp").compile(model)
    assert ei.value.knob == "instruction_capacity"
    assert ei.value.required == 12  # the full stream depth


def test_tmprogram_rejects_inconsistent_dims():
    """A CRC-consistent blob whose dims lie about the stream length must
    be rejected, not silently truncated to a wrong model."""
    import struct
    import zlib

    rng = np.random.default_rng(14)
    _, _, model = _random_model(rng, 4, 8, 32)
    blob = TMProgram(CapacityPlan.for_models([model]), model).to_bytes()
    payload = bytearray(blob[16:])
    # dims claim FEWER instructions than the payload carries, with the
    # CRC recomputed so only the length cross-check can catch the lie
    payload[36:40] = struct.pack("<I", model.n_instructions - 100)
    rebuilt = struct.pack(
        "<4sHHII", b"TMPG", 1, 0, len(payload), zlib.crc32(bytes(payload))
    ) + bytes(payload)
    with pytest.raises(ValueError, match="inconsistent"):
        TMProgram.from_bytes(rebuilt)


def test_failed_publication_restores_worker_state():
    """When the publication gate refuses a recal (capacity exhausted),
    the live slot is untouched AND the worker reverts to its pre-recal
    state — the unpublished fine-tune must not seed the next attempt."""
    import jax

    from repro.recal import RecalController, RecalWorker
    from repro.recal.compressor import Compressor

    cfg = TMConfig(n_classes=3, n_clauses=4, n_features=16)
    worker = RecalWorker(cfg, key=jax.random.key(3))
    plan = CapacityPlan(
        instruction_capacity=1024, feature_capacity=16, class_capacity=4,
        clause_capacity=4, include_capacity=16, batch_words=1,
    )
    acc = Accelerator(plan, engine="plan")
    controller = RecalController(
        acc, "s", worker, min_buffer_rows=1, epochs_per_recal=1,
        train_batch_size=8,
    )
    controller.deploy()
    rng = np.random.default_rng(15)
    x = rng.integers(0, 2, (16, 16)).astype(np.uint8)
    y = rng.integers(0, 3, 16).astype(np.int32)
    controller.observe(x, y)
    pre_state = worker.snapshot()
    pre_version = acc.registry.get("s").version
    # cripple the gate: an envelope no 3-class model can fit
    controller.compressor = Compressor(plan=dataclasses.replace(
        plan, class_capacity=1,
    ))
    with pytest.raises(CapacityExceeded):
        controller.recalibrate(reason="test")
    assert np.array_equal(worker.snapshot(), pre_state)
    assert acc.registry.get("s").version == pre_version


def test_compile_refuses_oversized_model():
    rng = np.random.default_rng(7)
    _, _, small = _random_model(rng, 3, 6, 24)
    _, _, big = _random_model(rng, 12, 6, 24)
    plan = dataclasses.replace(
        CapacityPlan.for_models([small]), instruction_capacity=8192
    )
    acc = Accelerator(plan, engine="plan")
    with pytest.raises(CapacityExceeded) as ei:
        acc.compile(big)
    assert ei.value.knob == "class_capacity"
    assert ei.value.required == 12


# ---------------------------------------------------------------------------
# engine plugin protocol
# ---------------------------------------------------------------------------

def test_engine_auto_selection_is_deterministic():
    plan = CapacityPlan(
        instruction_capacity=512, feature_capacity=64, class_capacity=8,
        clause_capacity=16, include_capacity=16, batch_words=1,
    )
    # no mesh: the fastest mesh-free engine, stable across calls
    assert select_engine(plan) == "popcount"
    assert all(select_engine(plan) == "popcount" for _ in range(5))
    # a mesh makes the mesh-consuming plugin the eligible set
    from repro.dist.sharding import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    assert select_engine(plan, mesh=mesh) == "sharded"
    acc = Accelerator(plan)
    assert acc.engine.name == "popcount"
    assert acc.engine.supports_donation
    assert Accelerator(plan, engine="interp").engine.name == "interp"


def test_register_engine_rejects_name_collisions():
    with pytest.raises(ValueError, match="already registered"):
        @register_engine("popcount")
        class Impostor(EngineBase):
            pass
    assert ENGINES["popcount"].__name__ == "PopcountEngine"


def test_make_engine_uniform_construction_and_options():
    plan = CapacityPlan(
        instruction_capacity=256, feature_capacity=32, class_capacity=4,
        clause_capacity=8, include_capacity=8, batch_words=1,
    )
    eng = make_engine("popcount", plan, implementation="xla")
    assert eng.implementation == "xla"
    # instance passthrough
    assert make_engine(eng, plan) is eng
    with pytest.raises(ValueError, match="unknown engine"):
        make_engine("fpga", plan)
    # capability flags live on the classes
    assert ENGINES["sharded"].needs_mesh
    assert not ENGINES["plan"].needs_mesh
    assert ENGINES["popcount"].supports_donation


def test_pallas_popcount_needs_a_tpu_or_explicit_interpret():
    """No hidden fallback: off-TPU the Pallas kernel is refused unless
    the caller asks for interpret mode, which is then bit-exact with the
    XLA twin through the engine path."""
    plan = CapacityPlan(
        instruction_capacity=256, feature_capacity=32, class_capacity=4,
        clause_capacity=8, include_capacity=8, batch_words=1,
    )
    with pytest.raises(ValueError, match="needs a TPU"):
        make_engine("popcount", plan, implementation="pallas")
    emu = make_engine(
        "popcount", plan, implementation="pallas", interpret=True
    )
    twin = make_engine("popcount", plan, implementation="xla")
    cfg = TMConfig(n_classes=4, n_clauses=8, n_features=32)
    rng = np.random.default_rng(5)
    model = encode(cfg, rng.random((4, 8, 64)) < 0.1)
    x = rng.integers(0, 2, (20, 32)).astype(np.uint8)
    got = emu.class_sums(emu.program(model), x)
    assert np.array_equal(got, twin.class_sums(twin.program(model), x))


def test_donation_warning_suppression_is_scoped_to_dispatch():
    """The donating engine must not leave donation-warning suppression
    in the process-global filter list after a call (the old module-level
    filterwarnings bug): the filter set is bit-identical before and
    after an engine dispatch."""
    rng = np.random.default_rng(11)
    cfg, acts, model = _random_model(rng, 3, 6, 24)
    acc = Accelerator.for_models([model], engine="popcount", batch_words=1)
    acc.load("m", acc.compile(model))
    before = list(warnings.filters)
    X = rng.integers(0, 2, (5, 24)).astype(np.uint8)
    assert (acc.class_sums("m", X) == _oracle_sums(cfg, acts, X)).all()
    assert warnings.filters == before


@pytest.mark.parametrize("engine", ENGINE_NAMES)
def test_hot_swap_differently_sized_models_one_plan(engine):
    """Acceptance: differently-sized models hot-swap within ONE
    negotiated plan with compile_cache_size() == 1 throughout."""
    rng = np.random.default_rng(8)
    shapes = [(5, 12, 40), (3, 8, 24), (7, 10, 56)]
    trained = [_random_model(rng, *s) for s in shapes]
    acc = Accelerator.for_models(
        [m for _, _, m in trained], engine=engine, batch_words=2
    )
    for cfg, acts, model in trained:
        acc.load("slot", acc.compile(model))
        X = rng.integers(0, 2, (21, cfg.n_features)).astype(np.uint8)
        assert (
            acc.infer("slot", X) == _oracle_sums(cfg, acts, X).argmax(1)
        ).all()
    assert acc.compile_cache_size() == 1
    assert acc.registry.get("slot").version == len(shapes)


# ---------------------------------------------------------------------------
# registry satellites: history depth + rollback provenance chain
# ---------------------------------------------------------------------------

def _tiny_models(n, seed=9):
    rng = np.random.default_rng(seed)
    return [_random_model(rng, 3, 4, 8, density=0.2)[2] for _ in range(n)]


def test_registry_history_depth_is_constructor_argument():
    plan = CapacityPlan(
        instruction_capacity=64, feature_capacity=16, class_capacity=4,
        clause_capacity=4, include_capacity=4, batch_words=1,
    )
    models = _tiny_models(5)
    for depth in (1, 3):
        reg = ModelRegistry(make_engine("plan", plan), history_depth=depth)
        for m in models:
            reg.install("s", m)
        assert len(reg.history("s")) == depth
    with pytest.raises(ValueError, match="history_depth"):
        ModelRegistry(make_engine("plan", plan), history_depth=0)
    server = TMServer(plan, backend="plan", history_depth=2)
    for m in models:
        server.register("s", m)
    assert len(server.registry.history("s")) == 2


def test_rollback_of_rollback_records_full_chain():
    plan = CapacityPlan(
        instruction_capacity=64, feature_capacity=16, class_capacity=4,
        clause_capacity=4, include_capacity=4, batch_words=1,
    )
    server = TMServer(plan, backend="plan")
    m1, m2, m3 = _tiny_models(3, seed=10)
    server.register("s", m1, provenance="deploy")          # v1
    server.register("s", m2, provenance="recal:drift")     # v2
    e3 = server.rollback("s")                              # v3 = m1
    assert e3.provenance == "rollback:v2->v1(deploy)"
    server.register("s", m3, provenance="recal:retry")     # v4
    e5 = server.rollback("s")                              # v5 = v3 entry
    # the chain survives: rolling back to a rollback shows BOTH hops
    assert e5.provenance == "rollback:v4->v3(rollback:v2->v1(deploy))"
    assert e5.model is m1
    assert e5.version == 5
