"""Property test (hypothesis): the popcount bitplane path is bit-exact.

For random capacities, odd word counts, ragged batches and all-excluded
clause banks, the four compressed execution strategies must agree on the
class sums EXACTLY:

    kernels.tm_popcount (Pallas, interpret=True on CPU — tier-1 covers it)
 == kernels.tm_popcount_xla (the portable serving formulation)
 == kernels.tm_interp (Pallas interpreter kernel, interpret=True)
 == core.interp.plan_class_sums (gather/segmented-reduce engine)

and all must match the dense ``batch_class_sums`` oracle.
"""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import TMConfig, batch_class_sums
from repro.core.compress import decode_to_plan, encode
from repro.core.interp import pad_plan, plan_class_sums
from repro.core.tm import literals
from repro.kernels.tm_interp.kernel import tm_interp
from repro.kernels.tm_interp.ops import (
    pack_interleaved_literals,
    plan_to_operands,
)
from repro.kernels.tm_popcount.kernel import tm_popcount, tm_popcount_xla
from repro.kernels.tm_popcount.ops import plan_to_popcount_operands


@st.composite
def popcount_case(draw):
    M = draw(st.integers(1, 5))
    C = draw(st.integers(1, 8))
    F = draw(st.integers(2, 40))
    # odd word counts and ragged (non-multiple-of-32) batches both matter:
    # the packers pad the trailing word, the kernels pad the word grid
    B = draw(st.integers(1, 100))
    density = draw(st.sampled_from([0.0, 0.03, 0.1, 0.3]))  # 0.0: all-excl
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    acts = rng.random((M, C, 2 * F)) < density
    X = rng.integers(0, 2, (B, F)).astype(np.uint8)
    return TMConfig(n_classes=M, n_clauses=C, n_features=F), acts, X


@settings(max_examples=15, deadline=None)
@given(popcount_case())
def test_popcount_matches_interp_and_plan(case):
    cfg, acts, X = case
    M, B = cfg.n_classes, X.shape[0]
    state = jnp.where(jnp.asarray(acts), cfg.n_states + 1, cfg.n_states)
    oracle = np.asarray(batch_class_sums(cfg, state, jnp.asarray(X)))

    plan = decode_to_plan(encode(cfg, np.asarray(acts)))
    m_cap = M + 2
    i_cap = max(64, -(-max(plan.n_includes, 1) // 64) * 64)
    packed = pack_interleaved_literals(jnp.asarray(X))  # pads B to words

    pc_ops = plan_to_popcount_operands(
        plan, i_cap, m_cap, l2_cap=int(packed.shape[0])
    )
    pc_args = tuple(jnp.asarray(a) for a in pc_ops) + (packed,)
    out_pallas = np.asarray(
        tm_popcount(*pc_args, block_instructions=64, interpret=True)
    )
    out_xla = np.asarray(tm_popcount_xla(*pc_args))

    it_args = tuple(
        jnp.asarray(a) for a in plan_to_operands(plan, i_cap, m_cap=m_cap)
    ) + (packed,)
    out_interp = np.asarray(tm_interp(
        *it_args, m_cap=m_cap, block_instructions=64, block_words=1,
        interpret=True,
    ))

    ncl_cap = max(8, plan.n_clauses_total)
    li, ci, cc, cp = pad_plan(plan, i_cap, ncl_cap)
    out_plan = np.asarray(plan_class_sums(
        jnp.asarray(li), jnp.asarray(ci), jnp.asarray(cc), jnp.asarray(cp),
        literals(jnp.asarray(X)), n_clause_cap=ncl_cap, m_cap=m_cap,
    ))  # [B, m_cap]

    assert (out_pallas == out_xla).all()
    assert (out_pallas == out_interp).all()
    assert (out_pallas[:, :B].T[:, :m_cap] == out_plan[:B]).all()
    assert (out_pallas[:M, :B].T == oracle).all()
