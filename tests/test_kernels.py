"""Pallas kernel validation (interpret=True on CPU): shape/dtype sweeps
against the pure-jnp ref oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TMConfig, batch_class_sums, pack_literals
from repro.core.compress import encode, decode_to_plan
from repro.kernels.clause_eval.kernel import clause_eval
from repro.kernels.clause_eval.ops import tm_dense_class_sums
from repro.kernels.clause_eval.ref import clause_eval_ref
from repro.kernels.tm_interp.kernel import tm_interp
from repro.kernels.tm_interp.ops import (
    pack_interleaved_literals,
    plan_to_operands,
)
from repro.kernels.tm_interp.ref import tm_interp_ref
from repro.kernels.tm_popcount.kernel import (
    bit_transpose32,
    kernel_blocks,
    kernel_operands,
    sum_weight_planes,
    tm_popcount,
    tm_popcount_resident,
    tm_popcount_xla,
)
from repro.kernels.tm_popcount.ops import (
    plan_to_popcount_operands,
    tm_popcount_class_sums,
)
from repro.kernels.tm_popcount.ref import tm_popcount_ref
from repro.kernels.tuning import choose_blocks

rng = np.random.default_rng(11)


@pytest.mark.parametrize(
    "nc,l2,w,bc,bw",
    [
        (8, 16, 1, 8, 1),
        (100, 64, 3, 32, 2),
        (256, 128, 8, 64, 4),
        (33, 30, 2, 16, 2),  # non-divisible padding path
        (5, 8, 1, 128, 8),  # block bigger than data
    ],
)
def test_clause_eval_shapes(nc, l2, w, bc, bw):
    actions = (rng.random((nc, l2)) < 0.15).astype(np.int32)
    lits = rng.integers(0, 2**32, (l2, w), dtype=np.uint32)
    out_k = clause_eval(
        jnp.asarray(actions), jnp.asarray(lits),
        block_clauses=bc, block_words=bw, interpret=True,
    )
    out_r = clause_eval_ref(jnp.asarray(actions), jnp.asarray(lits))
    assert (np.asarray(out_k) == np.asarray(out_r)).all()


def test_clause_eval_empty_clause_is_zero():
    actions = np.zeros((4, 16), np.int32)
    lits = np.full((16, 2), 0xFFFFFFFF, np.uint32)
    out = clause_eval(jnp.asarray(actions), jnp.asarray(lits), interpret=True)
    assert (np.asarray(out) == 0).all()


def test_dense_kernel_full_pipeline_vs_oracle():
    cfg = TMConfig(n_classes=6, n_clauses=16, n_features=40)
    acts = rng.random((6, 16, 80)) < 0.1
    X = rng.integers(0, 2, (96, 40)).astype(np.uint8)
    state = jnp.where(jnp.asarray(acts), cfg.n_states + 1, cfg.n_states)
    oracle = np.asarray(batch_class_sums(cfg, state, jnp.asarray(X)))
    sums = np.asarray(
        tm_dense_class_sums(
            jnp.asarray(acts).astype(jnp.int32), pack_literals(jnp.asarray(X)),
            n_classes=6, interpret=True,
        )
    )
    assert (sums.T[:96] == oracle).all()


@pytest.mark.parametrize(
    "M,C,F,B,bi,bw",
    [
        (4, 12, 25, 64, 64, 1),
        (3, 8, 100, 32, 128, 1),
        (6, 20, 60, 128, 256, 2),
        (2, 4, 10, 96, 32, 4),  # word blocking
    ],
)
def test_tm_interp_kernel_vs_oracle(M, C, F, B, bi, bw):
    cfg = TMConfig(n_classes=M, n_clauses=C, n_features=F)
    acts = rng.random((M, C, 2 * F)) < 0.08
    X = rng.integers(0, 2, (B, F)).astype(np.uint8)
    state = jnp.where(jnp.asarray(acts), cfg.n_states + 1, cfg.n_states)
    oracle = np.asarray(batch_class_sums(cfg, state, jnp.asarray(X)))
    plan = decode_to_plan(encode(cfg, np.asarray(acts)))
    lits = pack_interleaved_literals(jnp.asarray(X))
    i_cap = max(bi, -(-plan.n_includes // bi) * bi)
    sums = np.asarray(
        tm_interp(
            *(jnp.asarray(a) for a in plan_to_operands(plan, i_cap)),
            lits, m_cap=8, block_instructions=bi, block_words=bw,
            interpret=True,
        )
    )
    assert (sums[:M, :B].T == oracle).all()


def test_tm_interp_kernel_vs_ref_module():
    """Kernel vs its own ref.py oracle on raw operands."""
    n_inc, L2, W, M = 256, 64, 2, 8
    lit_idx = rng.integers(0, L2, n_inc).astype(np.int32)
    last = (rng.random(n_inc) < 0.2).astype(np.int32)
    last[-1] = 1
    pol = np.where(rng.random(n_inc) < 0.5, 1, -1).astype(np.int32)
    cls = np.sort(rng.integers(0, M, n_inc)).astype(np.int32)
    lits = rng.integers(0, 2**32, (L2, W), dtype=np.uint32)
    args = tuple(jnp.asarray(a) for a in (lit_idx, last, pol, cls))
    out_k = tm_interp(*args, jnp.asarray(lits), m_cap=M,
                      block_instructions=64, block_words=1, interpret=True)
    out_r = tm_interp_ref(*args, jnp.asarray(lits), m_cap=M)
    assert (np.asarray(out_k) == np.asarray(out_r)).all()


def test_bit_transpose32_spec_and_involution():
    """out[b] bit j == in[j] bit b; applying twice is the identity."""
    x = rng.integers(0, 2**32, (3, 32, 2), dtype=np.uint32)
    y = np.asarray(bit_transpose32(jnp.asarray(x), axis=1))
    for b in range(32):
        for j in range(32):
            assert ((y[:, b, :] >> j) & 1 == (x[:, j, :] >> b) & 1).all()
    z = np.asarray(bit_transpose32(jnp.asarray(y), axis=1))
    assert (z == x).all()


@pytest.mark.parametrize(
    "M,C,F,B,bi,bw",
    [
        (4, 12, 25, 64, 64, 2),
        (3, 8, 100, 32, 128, 1),
        (6, 20, 60, 128, 96, 4),
        (2, 4, 10, 96, 32, 4),  # block_words clips to the word count
        (2, 4, 10, 32 * 160, 32, 128),  # word blocking: two 128-word blocks
        (5, 6, 33, 32, 64, 1),  # i_cap not 32-aligned (padding path)
    ],
)
def test_tm_popcount_kernel_vs_oracle(M, C, F, B, bi, bw):
    """Pallas kernel == XLA twin == mask-domain ref == tm_interp ref ==
    dense oracle, over the full encode->plan->operand pipeline."""
    cfg = TMConfig(n_classes=M, n_clauses=C, n_features=F)
    acts = rng.random((M, C, 2 * F)) < 0.08
    X = rng.integers(0, 2, (B, F)).astype(np.uint8)
    state = jnp.where(jnp.asarray(acts), cfg.n_states + 1, cfg.n_states)
    oracle = np.asarray(batch_class_sums(cfg, state, jnp.asarray(X)))
    plan = decode_to_plan(encode(cfg, np.asarray(acts)))
    lits = pack_interleaved_literals(jnp.asarray(X))
    i_cap = max(bi, -(-max(plan.n_includes, 1) // bi) * bi) + 7  # unaligned
    m_cap = 8
    ops = plan_to_popcount_operands(
        plan, i_cap, m_cap, l2_cap=int(lits.shape[0])
    )
    args = tuple(jnp.asarray(a) for a in ops) + (lits,)
    out_k = np.asarray(tm_popcount(
        *args, block_instructions=bi, block_words=bw, interpret=True
    ))
    out_x = np.asarray(tm_popcount_xla(*args))
    out_r = np.asarray(tm_popcount_ref(*args))
    li, la, po, cl = plan_to_operands(plan, i_cap, m_cap=m_cap)
    out_i = np.asarray(tm_interp_ref(
        jnp.asarray(li), jnp.asarray(la), jnp.asarray(po), jnp.asarray(cl),
        lits, m_cap=m_cap,
    ))
    assert (out_k[:M, :B].T == oracle).all()
    assert (out_x == out_k).all()
    assert (out_r == out_k).all()
    assert (out_i == out_k).all()


def test_tm_popcount_autotuned_blocks_and_ops_entrypoint():
    """Default (table-chosen) blocks and both implementations agree."""
    cfg = TMConfig(n_classes=3, n_clauses=10, n_features=48)
    acts = rng.random((3, 10, 96)) < 0.1
    X = rng.integers(0, 2, (64, 48)).astype(np.uint8)
    plan = decode_to_plan(encode(cfg, np.asarray(acts)))
    lits = pack_interleaved_literals(jnp.asarray(X))
    a = tm_popcount_class_sums(
        plan, lits, m_cap=4, i_cap=512, implementation="pallas",
        interpret=True,
    )
    b = tm_popcount_class_sums(
        plan, lits, m_cap=4, i_cap=512, implementation="xla"
    )
    assert (np.asarray(a) == np.asarray(b)).all()
    with pytest.raises(ValueError, match="implementation"):
        tm_popcount_class_sums(plan, lits, m_cap=4, i_cap=512,
                               implementation="cuda")


def test_tm_popcount_all_excluded_is_zero():
    cfg = TMConfig(n_classes=4, n_clauses=6, n_features=16)
    plan = decode_to_plan(encode(cfg, np.zeros((4, 6, 32), bool)))
    lits = jnp.full((32, 2), 0xFFFFFFFF, jnp.uint32)
    out = tm_popcount_class_sums(plan, lits, m_cap=4, i_cap=64,
                                 implementation="xla")
    assert (np.asarray(out) == 0).all()


def test_program_build_rejects_out_of_range_class_ids():
    """The satellite bugfix: a malformed program must raise at build time
    (naming the instruction), never silently clamp into a live sum row."""
    cfg = TMConfig(n_classes=4, n_clauses=4, n_features=8)
    acts = rng.random((4, 4, 16)) < 0.3
    plan = decode_to_plan(encode(cfg, np.asarray(acts)))
    with pytest.raises(ValueError, match=r"instruction \d+: class id"):
        plan_to_operands(plan, 128, m_cap=2)
    with pytest.raises(ValueError, match=r"instruction \d+: class id"):
        plan_to_popcount_operands(plan, 128, 2)
    with pytest.raises(ValueError, match=r"literal slot"):
        plan_to_popcount_operands(plan, 128, 8, l2_cap=4)
    # in-range capacities still build
    plan_to_operands(plan, 128, m_cap=4)
    plan_to_popcount_operands(plan, 128, 4, l2_cap=16)


def test_choose_blocks_table():
    for n_inst, n_words in [(32, 1), (100, 3), (512, 2), (4096, 4),
                            (10000, 16)]:
        bi, bw = choose_blocks(n_inst, n_words)
        assert bi % 32 == 0 and bi >= 32
        assert 1 <= bw <= n_words
        assert bw == n_words or bw % 128 == 0  # a whole dim or whole tiles
        assert bi <= -(-n_inst // 32) * 32
    # the one shape, clipped to the capacity point
    assert choose_blocks(256, 1) == (256, 1)
    assert choose_blocks(17024, 4) == (512, 4)
    assert choose_blocks(4096, 300) == (512, 128)
    assert choose_blocks(40, 8) == (64, 8)
    with pytest.raises(ValueError, match="positive"):
        choose_blocks(0, 4)


@pytest.mark.parametrize(
    "bi,bw,match",
    [
        (64, 2, "block_words"),  # neither all 4 words nor 128-lane tiles
        (64, 0, "block_words"),
        (0, 4, "block_instructions"),
        (48, 4, "block_instructions"),
    ],
)
def test_tm_popcount_rejects_blocks_off_the_tiling(bi, bw, match):
    """Blocks the TPU compiler would refuse are refused up front."""
    lit_idx = jnp.zeros((64,), jnp.int32)
    masks = jnp.zeros((2, 2), jnp.uint32)
    lits = jnp.zeros((8, 4), jnp.uint32)
    with pytest.raises(ValueError, match=match):
        tm_popcount(lit_idx, lit_idx, masks, masks, lits,
                    block_instructions=bi, block_words=bw, interpret=True)


def test_tm_popcount_resident_layout_matches_per_call_layout():
    """The serving engine's once-per-program layout (numpy
    ``kernel_operands`` + ``tm_popcount_resident``) gives the same sums as
    ``tm_popcount`` laying the operands out per call, weighted masks
    included."""
    cfg = TMConfig(n_classes=3, n_clauses=8, n_features=20)
    acts = rng.random((3, 8, 40)) < 0.15
    X = rng.integers(0, 2, (64, 20)).astype(np.uint8)
    plan = decode_to_plan(encode(cfg, np.asarray(acts)))
    lits = pack_interleaved_literals(jnp.asarray(X))
    ops = plan_to_popcount_operands(plan, 200, 4, weight_planes=2)
    bi, bw = kernel_blocks(200, lits.shape[1], block_instructions=64)
    li, la, mp, mn = kernel_operands(*ops, bi)
    assert li.shape == (256,) and mp.shape == (256, 8)
    per_plane = tm_popcount_resident(
        *(jnp.asarray(a) for a in (li, la, mp, mn)), lits,
        block_instructions=bi, block_words=bw, interpret=True,
    )
    resident = sum_weight_planes(per_plane.reshape(2, 4, -1))
    per_call = tm_popcount(
        *(jnp.asarray(a) for a in ops), lits, block_instructions=64,
        interpret=True,
    )
    assert (np.asarray(resident) == np.asarray(per_call)).all()
    assert (np.asarray(per_call) == np.asarray(
        tm_popcount_xla(*(jnp.asarray(a) for a in ops), lits)
    )).all()


@pytest.mark.parametrize(
    "nc,l2,b,bc,bb,bk",
    [
        (8, 16, 32, 8, 16, 8),
        (100, 64, 96, 32, 32, 32),
        (256, 200, 128, 128, 128, 128),
        (33, 30, 40, 16, 16, 16),  # padding on every dim
    ],
)
def test_clause_matmul_kernel(nc, l2, b, bc, bb, bk):
    """MXU-formulated clause eval (kernels/clause_matmul) vs its ref."""
    from repro.kernels.clause_matmul.kernel import clause_matmul
    from repro.kernels.clause_matmul.ref import clause_matmul_ref

    actions = (rng.random((nc, l2)) < 0.15).astype(np.int32)
    lits = rng.integers(0, 2, (l2, b)).astype(np.int32)
    out_k = clause_matmul(
        jnp.asarray(actions), jnp.asarray(lits),
        block_c=bc, block_b=bb, block_k=bk, interpret=True,
    )
    out_r = clause_matmul_ref(jnp.asarray(actions), jnp.asarray(lits))
    assert (np.asarray(out_k) == np.asarray(out_r).astype(np.int32)).all()


def test_clause_matmul_full_pipeline():
    from repro.kernels.clause_matmul.ops import tm_matmul_class_sums

    cfg = TMConfig(n_classes=5, n_clauses=14, n_features=33)
    acts = rng.random((5, 14, 66)) < 0.1
    X = rng.integers(0, 2, (48, 33)).astype(np.uint8)
    state = jnp.where(jnp.asarray(acts), cfg.n_states + 1, cfg.n_states)
    oracle = np.asarray(batch_class_sums(cfg, state, jnp.asarray(X)))
    lits = np.stack([X, 1 - X], -1).reshape(48, -1).T.astype(np.int32)
    sums = np.asarray(
        tm_matmul_class_sums(
            jnp.asarray(acts).astype(jnp.int32), jnp.asarray(lits),
            n_classes=5, interpret=True,
        )
    )
    assert (sums[:, :48].T == oracle).all()
