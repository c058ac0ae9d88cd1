"""Sharded TM executor variants: all must reproduce dense TM inference
exactly (single-shard semantics tested here; mesh partitioning is covered
by test_sharding_dryrun.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.dist.tm_sharded as tms
from repro.core import TMConfig, batch_class_sums, pack_literals
from repro.core.compress import decode_to_plan, encode


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(5)
    cfg = TMConfig(n_classes=4, n_clauses=10, n_features=30)
    acts = rng.random((4, 10, 60)) < 0.25  # dense enough to span chunks
    X = rng.integers(0, 2, (64, 30)).astype(np.uint8)
    state = jnp.where(jnp.asarray(acts), cfg.n_states + 1, cfg.n_states)
    oracle = np.asarray(batch_class_sums(cfg, state, jnp.asarray(X)))
    plan = decode_to_plan(encode(cfg, np.asarray(acts)))
    return cfg, acts, X, oracle, plan


def _operands(plan, chunk):
    n_inc = plan.n_includes
    I_cap = -(-n_inc // chunk) * chunk
    lit_idx = np.zeros(I_cap, np.int32)
    lit_idx[:n_inc] = plan.lit_idx
    seg_last = np.zeros(I_cap, np.int32)
    seg_last[:n_inc][
        np.concatenate([plan.clause_id[1:] != plan.clause_id[:-1], [True]])
    ] = 1
    cid = np.full(I_cap, plan.n_clauses_total, np.int32)
    cid[:n_inc] = plan.clause_id
    return lit_idx, seg_last, cid


def test_unpacked_executor(case, monkeypatch):
    monkeypatch.setattr(tms, "CHUNK", 16)  # force chunk-spanning clauses
    cfg, acts, X, oracle, plan = case
    lit_idx, _, cid = _operands(plan, 16)
    lits = np.asarray(
        jax.vmap(lambda r: jnp.stack([r, ~r], -1).reshape(-1))(
            jnp.asarray(X, bool)
        )
    ).astype(np.int8)
    sums = np.asarray(
        tms._local_plan_executor(
            jnp.asarray(lit_idx), jnp.asarray(cid),
            jnp.asarray(plan.clause_class), jnp.asarray(plan.clause_pol),
            jnp.asarray(lits),
        )
    )
    assert (sums[: cfg.n_classes, :64].T == oracle).all()


def test_packed_executor(case, monkeypatch):
    monkeypatch.setattr(tms, "CHUNK", 16)
    cfg, acts, X, oracle, plan = case
    lit_idx, seg_last, _ = _operands(plan, 16)
    packed = pack_literals(jnp.asarray(X))
    sums = np.asarray(
        tms._local_plan_executor_packed(
            jnp.asarray(lit_idx), jnp.asarray(seg_last),
            jnp.asarray(plan.clause_class), jnp.asarray(plan.clause_pol),
            packed,
        )
    )
    assert (sums[: cfg.n_classes, :64].T == oracle).all()


def test_clausemajor_executor(case):
    cfg, acts, X, oracle, plan = case
    NCL = plan.n_clauses_total
    Lc = int(max((plan.clause_id == c).sum() for c in range(NCL)))
    pad_idx = np.full((NCL, Lc), 2 * cfg.n_features, np.int32)  # ones row
    for c in range(NCL):
        ks = plan.lit_idx[plan.clause_id == c]
        pad_idx[c, : len(ks)] = ks
    packed = np.asarray(pack_literals(jnp.asarray(X)))
    packed1 = np.concatenate(
        [packed, np.full((1, packed.shape[1]), 0xFFFFFFFF, np.uint32)]
    )
    sums = np.asarray(
        tms._local_plan_executor_clausemajor(
            jnp.asarray(pad_idx), jnp.asarray(plan.clause_class),
            jnp.asarray(plan.clause_pol), jnp.asarray(packed1),
        )
    )
    assert (sums[: cfg.n_classes, :64].T == oracle).all()


def test_moe_ep_matches_plain():
    """shard_map EP MoE == plain MoE (single-device degenerate mesh)."""
    import dataclasses

    from repro.configs.registry import get
    from repro.dist import sharding as shd
    from repro.models import moe

    cfg = dataclasses.replace(
        get("moonshot-v1-16b-a3b-smoke"), n_experts=4, top_k=2
    )
    rng = np.random.default_rng(0)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    p = {
        "router": jnp.asarray(rng.normal(size=(D, E)), jnp.float32),
        "w_gate": jnp.asarray(rng.normal(size=(E, D, F)) * 0.05, jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(E, D, F)) * 0.05, jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(E, F, D)) * 0.05, jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(2, 16, D)), jnp.float32)
    shd.set_activation_mesh(None)
    y_plain = moe.moe_ffn(p, x, cfg)
    mesh = shd.make_mesh((1, 1), ("data", "model"))
    shd.set_activation_mesh(mesh)
    try:
        with mesh:
            y_ep = jax.jit(lambda pp, xx: moe.moe_ffn(pp, xx, cfg))(p, x)
    finally:
        shd.set_activation_mesh(None)
    assert float(jnp.max(jnp.abs(y_plain - y_ep))) < 1e-5
