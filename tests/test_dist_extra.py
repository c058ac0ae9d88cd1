"""Coverage for repro.dist beyond the seed tests: batch-axis selection on
1-/2-/3-axis meshes, param sharding rules on a degenerate mesh, the sharded
TM executor against the dense oracle, and the dry-run lowering entry point.
"""

from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.dist.tm_sharded as tms
from repro.core import TMConfig, batch_class_sums
from repro.core.compress import decode_to_plan, encode
from repro.dist import sharding as shd


def _mesh_stub(shape, axes):
    """batch_axes only reads axis_names/devices.shape; a stub lets us probe
    multi-axis layouts without 8 host devices."""
    return SimpleNamespace(axis_names=axes, devices=np.empty(shape))


def test_batch_axes_mesh_ranks():
    # 1-axis data mesh
    assert shd.batch_axes(_mesh_stub((4,), ("data",)), 8) == ("data",)
    # 2-axis: model never carries batch
    assert shd.batch_axes(_mesh_stub((4, 2), ("data", "model")), 64) == ("data",)
    # 3-axis multi-pod layout
    m3 = _mesh_stub((2, 2, 2), ("pod", "data", "model"))
    assert shd.batch_axes(m3, 8) == ("pod", "data")
    # batch covers the pod axis but not pod*data -> shard pod only
    assert shd.batch_axes(m3, 2) == ("pod",)
    # indivisible batch stays replicated
    assert shd.batch_axes(m3, 3) is None
    assert shd.batch_axes(_mesh_stub((4, 2), ("data", "model")), 2) is None


def test_hint_noop_without_mesh():
    shd.set_activation_mesh(None)
    x = jnp.ones((4, 8))
    assert shd.hint(x, "batch", None) is x


def test_param_shardings_degenerate_mesh():
    """(1,1) mesh: every leaf gets exactly one sharding and the big matrices
    still carry the model axis in their spec (size-1 axes are free)."""
    from repro.configs.registry import get
    from repro.models.api import abstract_params

    mesh = shd.make_mesh((1, 1), ("data", "model"))
    cfg = get("starcoder2-7b")
    specs = abstract_params(cfg)
    sh = shd.param_shardings(cfg, mesh, specs)
    assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(specs))
    # embedding: vocab rows model-sharded (padded_vocab % n_model == 0)
    assert sh["embed"].spec[0] == "model"
    # attention + MLP matrices model-sharded somewhere past the stack dim
    for name in ("wq", "wk", "wv", "wo"):
        assert "model" in tuple(sh["layers"]["attn"][name].spec)
    for name in ("w_gate", "w_up", "w_down"):
        assert "model" in tuple(sh["layers"]["mlp"][name].spec)
    # norm scales replicated
    assert tuple(sh["final_norm"].spec) == ()
    # MoE expert stacks shard the expert dim
    moe_cfg = get("moonshot-v1-16b-a3b")
    moe_sh = shd.param_shardings(moe_cfg, mesh, abstract_params(moe_cfg))
    assert moe_sh["layers"]["moe"]["w_gate"].spec[1] == "model"


def test_build_tm_sharded_matches_oracle():
    """Single-device mesh: the sharded executor is bit-exact vs the dense
    oracle on decode_to_plan(encode(...)) output."""
    rng = np.random.default_rng(11)
    tmcfg = TMConfig(n_classes=3, n_clauses=8, n_features=20)
    acts = rng.random((3, 8, 40)) < 0.3
    X = rng.integers(0, 2, (32, 20)).astype(np.uint8)
    state = jnp.where(jnp.asarray(acts), tmcfg.n_states + 1, tmcfg.n_states)
    oracle = np.asarray(batch_class_sums(tmcfg, state, jnp.asarray(X)))
    plan = decode_to_plan(encode(tmcfg, np.asarray(acts)))

    mesh = shd.make_mesh((1, 1), ("data", "model"))
    Lc = int(max(
        (plan.clause_id == c).sum() for c in range(plan.n_clauses_total)
    ))
    cfg = tms.TMShardedConfig(
        name="t", n_classes=3, n_clauses=8, n_features=20, batch=32,
        include_cap=Lc,
    )
    fn, specs = tms.build_tm_sharded(cfg, mesh)
    idx, pol, lits1 = tms.operands_from_plan(cfg, plan, X, mesh)
    for op, spec in zip((idx, pol, lits1), specs):
        assert tuple(op.shape) == tuple(spec.shape)
    with mesh:
        sums = np.asarray(jax.jit(fn)(idx, pol, lits1))
    assert (sums[:, : tmcfg.n_classes] == oracle).all()
    # padded class columns contribute nothing
    assert (sums[:, tmcfg.n_classes:] == 0).all()


def test_operands_capacity_errors():
    rng = np.random.default_rng(0)
    tmcfg = TMConfig(n_classes=2, n_clauses=4, n_features=10)
    acts = rng.random((2, 4, 20)) < 0.5
    plan = decode_to_plan(encode(tmcfg, np.asarray(acts)))
    mesh = shd.make_mesh((1, 1), ("data", "model"))
    cfg = tms.TMShardedConfig(
        name="t", n_classes=2, n_clauses=4, n_features=10, batch=32,
        include_cap=1,  # too small for density 0.5
    )
    X = rng.integers(0, 2, (32, 10)).astype(np.uint8)
    with pytest.raises(ValueError):
        tms.operands_from_plan(cfg, plan, X, mesh)


def test_dryrun_lowers_smoke_cell():
    """launch/dryrun.py imports and lowers a smoke config on a 1x1 mesh
    (the full-mesh compiles are the slow subprocess tests)."""
    from repro.configs.base import ShapeSpec
    from repro.configs.registry import get
    from repro.dist import sharding as shd_mod
    from repro.launch.dryrun import lower_cell

    mesh = shd.make_mesh((1, 1), ("data", "model"))
    cfg = get("stablelm-3b-smoke")
    try:
        lowered = lower_cell(cfg, ShapeSpec("t", 64, 8, "train"), mesh)
        assert "hlo" in lowered.as_text().lower()
    finally:
        shd_mod.set_activation_mesh(None)
