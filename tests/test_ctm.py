"""The Convolutional TM (``core.tm.PatchGeometry``) on the serving path:
the program's convolutional oracle, the popcount engine's position axis
(XLA twin and Pallas kernel in interpret mode) through
``Accelerator.submit``, the TMProgram v3 artifact, the position and input
knobs of the envelope, and the refusal of every engine or train path that
cannot evaluate positions."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.accel import Accelerator, CapacityPlan, TMProgram, make_engine
from repro.core import TMConfig, batch_class_sums, state_from_actions
from repro.core.compress import encode
from repro.core.tm import (
    PatchGeometry,
    literals,
    pack_literals,
    pack_patch_literals,
    patches,
)
from repro.kernels.tm_popcount.kernel import tm_popcount, tm_popcount_xla
from repro.kernels.tm_popcount.ops import patch_operands

GEOM = PatchGeometry(8, 8, 3, 3)  # 36 positions, 9 + 5 + 5 features
IMPLEMENTATIONS = [{"implementation": "xla"},
                   {"implementation": "pallas", "interpret": True}]
IDS = ["xla", "pallas-interpret"]


def _actions(rng, m, c, f, per_clause=3):
    """One literal of each of ``per_clause`` features per clause, with a
    random sign (the ``feature`` rule)."""
    acts = np.zeros((m, c, 2 * f), bool)
    for i in range(m):
        for j in range(c):
            ks = rng.choice(f, per_clause, replace=False)
            acts[i, j, 2 * ks + rng.integers(0, 2, per_clause)] = True
    return acts


def _ctm(rng, geom=GEOM, m=3, c=8):
    cfg = TMConfig(m, c, geom.n_features, patch=geom)
    acts = _actions(rng, m, c, geom.n_features)
    return cfg, acts, encode(cfg, acts)


def _oracle(cfg, acts, x):
    return np.asarray(batch_class_sums(cfg, state_from_actions(cfg, acts), x))


def _one_position_rows(geom, acts):
    """Images on which clause 0 of class 0 fires at exactly one position.

    The clause is rewritten to include every window pixel, so it fires
    where the window is all ones; each image is zero but for one
    window-sized block of ones, so that is the only such position."""
    acts = acts.copy()
    acts[0, 0] = False
    acts[0, 0, 0:2 * geom.window_h * geom.window_w:2] = True
    rows = []
    for y, x in ((0, 0), (geom.positions_y - 1, geom.positions_x - 1),
                 (2, 3)):
        img = np.zeros((geom.image_h, geom.image_w), np.uint8)
        img[y:y + geom.window_h, x:x + geom.window_w] = 1
        rows.append(img.reshape(-1))
    return acts, np.stack(rows)


def test_patches_follow_the_geometry():
    """``patches`` (window slices) and ``feature_sources`` (index
    arithmetic) give the same features, in the documented order."""
    rng = np.random.default_rng(0)
    x = rng.integers(0, 2, (5, GEOM.input_width), dtype=np.uint8)
    got = np.asarray(patches(GEOM, x)).astype(np.uint8)
    assert got.shape == (5, GEOM.n_positions, GEOM.n_features)
    ext = np.concatenate([x, np.zeros((5, 1), np.uint8),
                          np.ones((5, 1), np.uint8)], axis=1)
    assert np.array_equal(got, ext[:, GEOM.feature_sources()].transpose(0, 2, 1))
    # position (y, x) = (2, 4): window pixels, then y > t, then x > t
    p = 2 * GEOM.positions_x + 4
    img = x[0].reshape(8, 8)
    want = np.concatenate([img[2:5, 4:7].reshape(-1),
                           (2 > np.arange(5)), (4 > np.arange(5))])
    assert np.array_equal(got[0, p], want.astype(np.uint8))
    assert (GEOM.n_positions, GEOM.n_features) == (36, 19)
    mnist = PatchGeometry(28, 28, 10, 10)
    assert (mnist.n_positions, mnist.n_features, mnist.input_width) == (
        361, 136, 784)


def test_pack_patch_literals_packs_every_patch():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 2, (64, GEOM.input_width), dtype=np.uint8)
    sources, valid = patch_operands(GEOM, 80, 24, 40)
    got = np.asarray(pack_patch_literals(
        jnp.asarray(np.pad(x, ((0, 0), (0, 16)))), jnp.asarray(sources),
        jnp.asarray(valid)))
    assert got.shape == (48, 40, 2)
    lits = literals(patches(GEOM, x))  # [B, P, 2F]
    for p in (0, 17, 35):
        want = np.asarray(pack_literals(jnp.asarray(
            np.asarray(patches(GEOM, x))[:, p])))
        assert np.array_equal(got[:2 * GEOM.n_features, p], want)
        assert lits.shape[1] == 36
    assert not got[:, 36:].any()  # padded positions: both literals 0


def _naive_sums(lit_idx, last, mask_pos, mask_neg, lits):
    """The kernel's contract, one instruction at a time: AND the literal
    words at every position, and at a clause's last include OR them over
    positions and add the clause's vote to each datapoint it fires on."""
    l2, p, w = lits.shape
    ones = np.full((p, w), 0xFFFFFFFF, np.uint32)
    acc, sums = ones, np.zeros((mask_pos.shape[0], 32 * w), np.int64)
    for t in range(lit_idx.shape[0]):
        acc = acc & lits[lit_idx[t]]
        if last[t] == 1:
            word = np.bitwise_or.reduce(acc, axis=0)  # [W]
            bits = (word[:, None] >> np.arange(32, dtype=np.uint32)) & 1
            chunk, bit = divmod(t, 32)
            vote = (((mask_pos[:, chunk] >> bit) & 1).astype(np.int64)
                    - ((mask_neg[:, chunk] >> bit) & 1))
            sums += vote[:, None] * bits.reshape(-1)[None, :]
            acc = ones
    return sums


@pytest.mark.parametrize("n_words", [1, 3, 4])
def test_kernel_position_axis_matches_its_xla_twin(n_words):
    """The Pallas kernel (interpret mode) with a position axis equals the
    XLA twin, across word counts the OR over positions pads to a power of
    two, and with one position equals the plain kernel."""
    rng = np.random.default_rng(n_words)
    i_cap, m_cap, l2, p = 96, 3, 20, 7
    lit_idx = rng.integers(0, l2, i_cap).astype(np.int32)
    last = (rng.random(i_cap) < 0.3).astype(np.int32)
    mask_pos = rng.integers(0, 2**32, (m_cap, 3), dtype=np.uint32)
    mask_neg = rng.integers(0, 2**32, (m_cap, 3), dtype=np.uint32) & ~mask_pos
    # sparse words, so that clause words are not all zero after the ANDs
    lits = (rng.random((l2, p, n_words, 32)) < 0.85).astype(np.uint32)
    lits = (lits << np.arange(32, dtype=np.uint32)).sum(-1, dtype=np.uint32)
    args = [jnp.asarray(a) for a in (lit_idx, last, mask_pos, mask_neg)]
    want = np.asarray(tm_popcount_xla(*args, jnp.asarray(lits)))
    got = np.asarray(tm_popcount(*args, jnp.asarray(lits), interpret=True))
    assert np.array_equal(got, want) and want.any()
    assert np.array_equal(want, _naive_sums(lit_idx, last, mask_pos,
                                            mask_neg, lits))
    flat = np.asarray(tm_popcount(*args, jnp.asarray(lits[:, 0]),
                                  interpret=True))
    assert np.array_equal(flat, np.asarray(tm_popcount(
        *args, jnp.asarray(lits[:, :1]), interpret=True)))


@pytest.mark.parametrize("positions", [0, 5])
def test_kernel_streams_a_long_program_in_smem_blocks(monkeypatch, positions):
    """A stream longer than ``PREFETCH_INSTRUCTIONS`` reaches the kernel
    one SMEM block per instruction block, with the same sums.  (Shapes of
    their own: the kernel reads the bound when it is traced.)"""
    from repro.kernels.tm_popcount import kernel

    monkeypatch.setattr(kernel, "PREFETCH_INSTRUCTIONS", 64)
    rng = np.random.default_rng(positions)
    i_cap, m_cap, l2 = 160, 2, 22
    lit_idx = rng.integers(0, l2, i_cap).astype(np.int32)
    last = (rng.random(i_cap) < 0.3).astype(np.int32)
    mask_pos = rng.integers(0, 2**32, (m_cap, 5), dtype=np.uint32)
    mask_neg = rng.integers(0, 2**32, (m_cap, 5), dtype=np.uint32) & ~mask_pos
    shape = (l2, positions, 2, 32) if positions else (l2, 1, 2, 32)
    lits = (rng.random(shape) < 0.85).astype(np.uint32)
    lits = (lits << np.arange(32, dtype=np.uint32)).sum(-1, dtype=np.uint32)
    args = [jnp.asarray(a) for a in (lit_idx, last, mask_pos, mask_neg)]
    panel = jnp.asarray(lits if positions else lits[:, 0])
    got = np.asarray(tm_popcount(*args, panel, block_instructions=64,
                                 interpret=True))
    assert np.array_equal(got, _naive_sums(lit_idx, last, mask_pos,
                                           mask_neg, lits))


@pytest.mark.parametrize("opts", IMPLEMENTATIONS, ids=IDS)
def test_served_ctm_equals_the_oracle(opts):
    rng = np.random.default_rng(3)
    cfg, acts, _ = _ctm(rng)
    acts, one_pos = _one_position_rows(GEOM, acts)
    model = encode(cfg, acts)
    x = np.concatenate([one_pos, rng.integers(
        0, 2, (45, GEOM.input_width), dtype=np.uint8)])
    acc = Accelerator.for_models([model], engine_options=opts)
    assert acc.engine.name == "popcount"
    assert (acc.plan.position_capacity, acc.plan.input_capacity) == (36, 64)
    acc.load("ctm", acc.compile(model).to_bytes())
    h = acc.submit("ctm", x)
    acc.flush()
    want = _oracle(cfg, acts, x)
    h.wait(30)
    assert np.array_equal(h.result(), np.argmax(want, axis=1))
    assert np.array_equal(h.class_sums, want)
    # the one-position rows fire clause 0 (a + vote) where nothing else may
    base = _oracle(cfg, np.where(np.arange(8)[None, :, None] == 0,
                                 False, acts), one_pos)
    assert np.array_equal(want[:3, 0] - base[:, 0], [1, 1, 1])


@pytest.mark.parametrize("geom", [PatchGeometry(5, 7, 2, 3),
                                  PatchGeometry(6, 4, 1, 1),
                                  PatchGeometry(4, 9, 4, 2)],
                         ids=["5x7-2x3", "6x4-1x1", "4x9-4x2"])
def test_served_ctm_equals_the_oracle_across_geometries(geom):
    """Non-square images and windows, a one-pixel window, and a window as
    tall as the image (one row of positions, no y bits)."""
    rng = np.random.default_rng(geom.n_positions)
    cfg, acts, model = _ctm(rng, geom=geom, m=2, c=6)
    x = rng.integers(0, 2, (40, geom.input_width), dtype=np.uint8)
    acc = Accelerator.for_models([model])
    acc.load("ctm", acc.compile(model))
    assert np.array_equal(acc.class_sums("ctm", x), _oracle(cfg, acts, x))


def test_one_position_geometry_serves_as_the_plain_tm():
    """A window as large as the image is the plain TM: same envelope,
    same class sums, one position in the batch span."""
    rng = np.random.default_rng(4)
    geom = PatchGeometry(4, 5, 4, 5)
    acts = _actions(rng, 3, 8, 20)
    plain = encode(TMConfig(3, 8, 20), acts)
    conv = encode(TMConfig(3, 8, 20, patch=geom), acts)
    assert conv.n_positions == 1 and conv.input_width == 20
    x = rng.integers(0, 2, (40, 20), dtype=np.uint8)
    plans, sums = [], []
    for model in (plain, conv):
        acc = Accelerator.for_models([model])
        acc.load("m", acc.compile(model))
        plans.append(acc.plan)
        sums.append(acc.class_sums("m", x))
    assert plans[0] == plans[1] and plans[0].position_capacity == 1
    assert np.array_equal(sums[0], sums[1])
    assert np.array_equal(sums[0], _oracle(TMConfig(3, 8, 20), acts, x))


def test_a_plain_tm_serves_in_a_convolutional_envelope():
    rng = np.random.default_rng(5)
    cfg, acts, ctm = _ctm(rng)
    plain_acts = _actions(rng, 2, 6, 40)
    plain = encode(TMConfig(2, 6, 40), plain_acts)
    acc = Accelerator.for_models([ctm, plain])
    assert acc.plan.feature_capacity == 48 and acc.plan.input_capacity == 64
    acc.load("ctm", acc.compile(ctm))
    acc.load("plain", acc.compile(plain))
    xc = rng.integers(0, 2, (33, 64), dtype=np.uint8)
    xp = rng.integers(0, 2, (33, 40), dtype=np.uint8)
    assert np.array_equal(acc.class_sums("ctm", xc), _oracle(cfg, acts, xc))
    assert np.array_equal(acc.class_sums("plain", xp),
                          _oracle(TMConfig(2, 6, 40), plain_acts, xp))
    assert acc.compile_cache_size() == 1


def test_program_v3_round_trips_a_ctm():
    rng = np.random.default_rng(6)
    _, _, model = _ctm(rng)
    art = Accelerator.for_models([model]).compile(model)
    assert art.format_version == 3 and art.model.patch == GEOM
    blob = art.to_bytes()
    back = TMProgram.from_bytes(blob)
    assert back == art and back.capacity == art.capacity
    assert back.model.patch == GEOM and back.to_bytes() == blob
    assert art.n_bytes == len(blob)
    # the geometry section sits after caps (9I), dims (4I), n_weights (I)
    geometry_at = 16 + 4 * (9 + 4 + 1)
    assert blob[geometry_at:geometry_at + 16] == np.asarray(
        [8, 8, 3, 3], "<u4").tobytes()
    bad = bytearray(blob)
    bad[geometry_at + 8] ^= 0x01  # window_h 3 -> 2
    with pytest.raises(ValueError, match="checksum mismatch"):
        TMProgram.from_bytes(bytes(bad))
    with pytest.raises(ValueError, match="cannot carry a patch"):
        TMProgram(capacity=art.capacity, model=model, format_version=2)
    # clause weights follow the stream, after the geometry section
    cfg, acts, _ = _ctm(rng)
    weighted = encode(cfg, acts, clause_weights=rng.integers(1, 4, (3, 8)))
    art = TMProgram(capacity=CapacityPlan.for_models([weighted]),
                    model=weighted)
    back = TMProgram.from_bytes(art.to_bytes())
    assert back == art and back.model.weighted and back.format_version == 3


def test_plain_models_keep_their_v1_and_v2_bytes():
    """Flat models never emit v3: a plain envelope serializes as before,
    and from_bytes restores the two new knobs at their plain values."""
    rng = np.random.default_rng(7)
    plain = encode(TMConfig(3, 8, 20), _actions(rng, 3, 8, 20))
    plan = CapacityPlan.for_models([plain])
    assert (plan.position_capacity, plan.input_capacity) == (
        1, plan.feature_capacity)
    art = TMProgram(capacity=plan, model=plain)
    assert art.format_version == 1
    assert TMProgram.from_bytes(art.to_bytes()).capacity == plan
    weighted = TMProgram(capacity=dataclasses.replace(plan, weight_planes=2),
                         model=plain)
    assert weighted.format_version == 2
    assert TMProgram.from_bytes(weighted.to_bytes()) == weighted
    # a plain model in a convolutional envelope is v3, and round-trips
    _, _, ctm = _ctm(rng)
    wide = TMProgram(capacity=CapacityPlan.for_models([ctm, plain]),
                     model=plain)
    assert wide.format_version == 3
    assert TMProgram.from_bytes(wide.to_bytes()) == wide


def test_for_models_negotiates_positions_and_input_width():
    rng = np.random.default_rng(8)
    _, _, ctm = _ctm(rng)
    big = PatchGeometry(10, 12, 4, 4)  # 63 positions, 16 + 6 + 8 features
    _, _, ctm2 = _ctm(rng, geom=big)
    plain = encode(TMConfig(2, 6, 70), _actions(rng, 2, 6, 70))
    plan = CapacityPlan.for_models([ctm, ctm2, plain])
    assert plan.position_capacity == 63
    assert plan.input_capacity == 128  # 120 image bits, quantized to 16
    assert plan.feature_capacity == 80  # the plain model's 70 features
    for model in (ctm, ctm2, plain):
        assert plan.fits(model)
    small = CapacityPlan.for_models([ctm])
    assert not small.fits(ctm2)
    assert [v[0] for v in small.violations(ctm2)] == [
        "position_capacity", "input_capacity"]
    assert small.widen_to(ctm2).position_capacity == 63
    assert plan.shrink_to(ctm).position_capacity == 36


@pytest.mark.parametrize("engine", ["interp", "plan", "sharded"])
def test_engines_without_positions_refuse_a_ctm(engine):
    rng = np.random.default_rng(9)
    _, _, model = _ctm(rng)
    plan = CapacityPlan.for_models([model])
    eng = make_engine(engine, plan)
    with pytest.raises(ValueError, match="convolutional TM with 36"):
        eng.program(model)
    acc = Accelerator(plan, engine=engine)
    with pytest.raises(ValueError, match="convolutional TM"):
        acc.compile(model)
    with pytest.raises(ValueError, match="convolutional TM"):
        acc.load("m", TMProgram(capacity=plan, model=model).to_bytes())


def test_train_paths_refuse_a_ctm():
    from repro.recal import RecalWorker
    from repro.recal.train_engine import (
        make_train_engine,
        select_train_engine,
        train_engine_names,
    )

    cfg = TMConfig(3, 8, GEOM.n_features, patch=GEOM)
    with pytest.raises(ValueError, match="convolutional TM"):
        select_train_engine(cfg)
    for name in train_engine_names():
        if name == "sharded":
            continue  # needs a mesh; refused by the same base class
        with pytest.raises(ValueError, match="convolutional TM"):
            make_train_engine(name, cfg)
    with pytest.raises(ValueError, match="convolutional TM"):
        RecalWorker(cfg)
    one = TMConfig(3, 8, 20, patch=PatchGeometry(4, 5, 4, 5))
    assert select_train_engine(one)  # one position trains as the plain TM


def test_geometry_is_validated():
    with pytest.raises(ValueError, match="window_h"):
        PatchGeometry(4, 4, 5, 2)
    with pytest.raises(ValueError, match="patch's feature count 19"):
        TMConfig(3, 8, 20, patch=GEOM)
