"""Sharding/dry-run machinery tests.

These run in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the flag must be set before jax initializes, and the main test process must
keep seeing 1 device), exercising lower+compile of smoke configs on a real
(4 data x 2 model) mesh including the multi-pod axis layout.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_smoke_cells_compile_on_mesh():
    out = _run("""
        import jax
        from repro.configs.registry import get
        from repro.configs.base import ShapeSpec
        from repro.dist.sharding import make_mesh
        from repro.launch.dryrun import lower_cell
        mesh = make_mesh((4, 2), ("data", "model"))
        for arch in ("starcoder2-7b", "moonshot-v1-16b-a3b", "zamba2-2.7b",
                     "whisper-medium", "xlstm-125m"):
            cfg = get(arch + "-smoke")
            for kind in ("train", "prefill", "decode"):
                lower_cell(cfg, ShapeSpec("t", 64, 8, kind), mesh).compile()
        print("COMPILED")
    """)
    assert "COMPILED" in out


@pytest.mark.slow
def test_multipod_axis_shards():
    """The pod axis actually shards the batch (proves the 3-axis layout)."""
    out = _run("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.configs.registry import get
        from repro.configs.base import ShapeSpec
        from repro.dist import sharding as shd
        mesh = shd.make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get("stablelm-3b-smoke")
        assert shd.batch_axes(mesh, 8) == ("pod", "data")
        from repro.launch.dryrun import lower_cell
        c = lower_cell(cfg, ShapeSpec("t", 64, 8, "train"), mesh).compile()
        print("PODOK", c.cost_analysis()["flops"] > 0)
    """)
    assert "PODOK True" in out


@pytest.mark.slow
def test_tm_sharded_compiles():
    """The paper's multi-core TM on a mesh (classes x batch)."""
    out = _run("""
        import jax, dataclasses
        from repro.dist.sharding import make_mesh
        from repro.dist.tm_sharded import TM_CONFIGS, build_tm_sharded
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = dataclasses.replace(TM_CONFIGS["tm-paper"], n_classes=2, batch=64)
        # adapt: model axis=2 shards 2 classes; data axis=4 shards batch
        fn, specs = build_tm_sharded(cfg, mesh)
        with mesh:
            compiled = jax.jit(fn).lower(*specs).compile()
        print("TMOK")
    """)
    assert "TMOK" in out


def test_collective_parser():
    from repro.analysis.roofline import collective_bytes

    hlo = """
  %p = f32[128,64]{1,0} parameter(0)
  %fusion.1 = f32[128,64]{1,0} fusion(%p), kind=kLoop
  %all-reduce.1 = f32[128,64]{1,0} all-reduce(%fusion.1), channel_id=1
  %ag = f32[512,64]{1,0} all-gather(%fusion.1), dims={0}
  ROOT %all-reduce.2 = f32[] all-reduce(%all-reduce.1), channel_id=2
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 128 * 64 * 4 * 2  # both operands resolved
    assert out["all-gather"] == 128 * 64 * 4  # operand, not result


def test_param_sharding_rules():
    import jax
    from repro.configs.registry import get
    from repro.dist import sharding as shd
    from repro.models.api import abstract_params

    mesh = shd.make_mesh((1, 1), ("data", "model"))
    for arch in ("starcoder2-7b", "llama4-maverick-400b-a17b", "zamba2-2.7b",
                 "xlstm-125m", "whisper-medium"):
        cfg = get(arch)
        specs = abstract_params(cfg)
        sh = shd.param_shardings(cfg, mesh, specs)
        # every leaf has a sharding; big matrices are model-sharded
        flat = jax.tree_util.tree_flatten_with_path(sh)[0]
        assert len(flat) == len(jax.tree.leaves(specs))


def test_cache_sharding_rules_head_dims():
    """Decode caches get batch+HEAD sharding for every cache family —
    attention KV at dim 3, SSM state / mLSTM matrix-memory at their own
    head dims — while headless leaves (SSM conv, sLSTM channel state)
    stay batch-only.  Runs on a degenerate (1, 1) named mesh: axis-name
    assignment is mesh-size-independent, so the PartitionSpecs prove the
    rule without 8 host devices."""
    import jax
    from repro.configs.base import ShapeSpec
    from repro.configs.registry import get
    from repro.dist import sharding as shd
    from repro.models.api import family_for
    from repro.models.ssm import ssm_dims

    mesh = shd.make_mesh((1, 1), ("data", "model"))

    def specs_for(arch, batch=8):
        cfg = get(arch)
        shape = ShapeSpec("t", 64, batch, "decode")
        c_specs = family_for(cfg).cache_specs(cfg, shape)
        c_sh = shd.cache_shardings(cfg, mesh, shape, c_specs)
        return cfg, shape, jax.tree.leaves(c_specs), jax.tree.leaves(c_sh)

    def model_dims(sh):
        return [
            d for d, ax in enumerate(sh.spec) if ax == "model"
        ]

    # dense KV [L, B, S, Hkv, hd]: batch dim 1, head dim 3
    cfg, shape, leaves, shardings = specs_for("starcoder2-7b")
    for leaf, sh in zip(leaves, shardings):
        assert sh.spec[1] is not None  # batch sharded
        assert model_dims(sh) == [3]
        assert leaf.shape[3] == cfg.n_kv_heads

    # xLSTM: mLSTM C/n/m [P, B, H, ...] head dim 2; sLSTM [P, B, D]
    # is per-channel fused state — batch-only
    cfg, shape, leaves, shardings = specs_for("xlstm-125m")
    for leaf, sh in zip(leaves, shardings):
        assert sh.spec[1] is not None
        if leaf.ndim >= 3 and leaf.shape[2] == cfg.n_heads:
            assert model_dims(sh) == [2], leaf.shape
        else:
            assert model_dims(sh) == [], leaf.shape

    # head-size collision: with d_model=64, n_heads=8 the mLSTM C cache
    # is [P, B, 8, 8, 8] — its per-head feature dims equal the head
    # count, so rank+size alone matches the KV dim-3 pin.  The square
    # trailing [hd, hd] signature must route it to the generic rule:
    # the TRUE head dim 2 shards, the feature dims stay replicated.
    import dataclasses

    collide = dataclasses.replace(
        get("xlstm-125m"), name="xlstm-collide", d_model=64, n_heads=8,
        n_kv_heads=8,
    )
    shape = ShapeSpec("t", 64, 16, "decode")
    c_specs = family_for(collide).cache_specs(collide, shape)
    c_sh = shd.cache_shardings(collide, mesh, shape, c_specs)
    for leaf, sh in zip(jax.tree.leaves(c_specs), jax.tree.leaves(c_sh)):
        assert sh.spec[1] is not None
        if leaf.ndim >= 3 and leaf.shape[2] == collide.n_heads:
            assert model_dims(sh) == [2], leaf.shape
        else:
            assert model_dims(sh) == [], leaf.shape

    # Zamba2 hybrid: SSM state [G, E, B, H, N, P] head dim 3, conv
    # [G, E, B, K-1, d_conv] batch-only, shared KV [G, B, W, Hkv, hd]
    cfg, shape, leaves, shardings = specs_for("zamba2-2.7b")
    H_ssm = ssm_dims(cfg)[1]
    saw_ssm_state = saw_kv = False
    for leaf, sh in zip(leaves, shardings):
        if leaf.ndim == 6:  # ssm state
            assert sh.spec[2] is not None  # batch at dim 2
            assert model_dims(sh) == [3] and leaf.shape[3] == H_ssm
            saw_ssm_state = True
        elif leaf.ndim == 5 and leaf.shape[3] == cfg.n_kv_heads:  # kv
            assert sh.spec[1] is not None
            assert model_dims(sh) == [3]
            saw_kv = True
        else:  # conv stack: no head dim
            assert model_dims(sh) == [], leaf.shape
    assert saw_ssm_state and saw_kv
