"""Jittable step builders shared by the launchers, the dry-run and tests.

``make_train_step`` supports gradient-accumulation microbatching (the
activation-memory knob recorded per-arch in configs as
``train_microbatches``): the global batch is split on its leading dim and
scanned, grads accumulated in fp32, then one AdamW update is applied.

``make_tm_train_step`` is the mesh-sharded Tsetlin Machine feedback step
(the Fig-8 training node scaled out): TA state shards its class dim over
``model``, the batch shards over the non-``model`` axes, per-sample
summed-delta feedback is computed locally and psum'd across the batch
axes.  Bit-identical to ``core.train.train_batch_parallel`` on any mesh
(integer deltas commute), which tests/test_recal.py asserts.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.train import sample_class_delta, sample_keys
from ..models.api import family_for
from ..optim import adamw
from .sharding import _axis_sizes, batch_axes, shard_map


def opt_config_for(cfg) -> adamw.AdamWConfig:
    """Per-arch optimizer config (moment dtype follows the HBM budget)."""
    moment_dtype = (
        jnp.bfloat16 if getattr(cfg, "moment_dtype", "float32") == "bfloat16"
        else jnp.float32
    )
    return adamw.AdamWConfig(moment_dtype=moment_dtype)


def make_train_step(
    cfg, opt_cfg: adamw.AdamWConfig, *, microbatches: int = 1
) -> Callable:
    """-> step(params, opt_state, batch) -> (params, opt_state, metrics)
    with metrics = {"loss", "grad_norm"}."""
    fam = family_for(cfg)

    def loss_fn(params, batch):
        return fam.loss(cfg, params, batch)

    def step(params, opt_state, batch):
        if microbatches > 1:
            def split(x):
                B = x.shape[0]
                assert B % microbatches == 0, (
                    f"global batch {B} not divisible by "
                    f"train_microbatches={microbatches}"
                )
                return x.reshape(microbatches, B // microbatches, *x.shape[1:])

            mb = jax.tree.map(split, batch)

            def body(carry, b):
                loss_acc, g_acc = carry
                loss, g = jax.value_and_grad(loss_fn)(params, b)
                g_acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), g_acc, g
                )
                return (loss_acc + loss.astype(jnp.float32), g_acc), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (loss_sum, g_sum), _ = jax.lax.scan(
                body, (jnp.float32(0.0), g0), mb
            )
            loss = loss_sum / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, g_sum)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_state, gnorm = adamw.apply(
            opt_cfg, params, grads, opt_state
        )
        return new_params, new_state, {"loss": loss, "grad_norm": gnorm}

    return step


def make_tm_train_step(tm_cfg, mesh, *, batch: int) -> Callable:
    """-> step(state, key, xb, yb) -> state, sharded over ``mesh``.

    ``state`` int32[M, C, 2F] shards classes over ``model``; ``xb``/``yb``
    shard their leading dim over the non-``model`` axes (``batch_axes``).
    Each device computes the summed-delta feedback of its batch shard
    restricted to its class rows (``core.train.sample_class_delta``), the
    deltas are psum'd over the batch axes, and one clipped update is
    applied — the large-class-count scale-out of the recal worker.

    Seeding follows the core contract: global sample ``i`` (its position
    in the UNSHARDED batch) trains under ``fold_in(key, i)``, so the
    result equals ``train_batch_parallel(cfg, state, key, xb, yb)``
    bit-exactly regardless of the mesh shape.
    """
    sizes = _axis_sizes(mesh)
    n_model = sizes.get("model", 1)
    M, N = tm_cfg.n_classes, tm_cfg.n_states
    if M % n_model:
        raise ValueError(
            f"the model axis size ({n_model}) must divide n_classes={M} for "
            f"the class-sharded TM train step; pad the config or shrink the "
            f"mesh"
        )
    bx = batch_axes(mesh, batch)
    has_model = "model" in sizes
    state_spec = P("model", None, None) if has_model else P()
    m_local = M // n_model

    def local(state_l, key, xb_l, yb_l):
        B_l = xb_l.shape[0]
        shard = jnp.int32(0)
        for ax in bx or ():
            shard = shard * sizes[ax] + jax.lax.axis_index(ax)
        keys = sample_keys(key, B_l, offset=shard * B_l)
        m0 = (
            jax.lax.axis_index("model") * m_local if has_model else jnp.int32(0)
        )
        m_ids = m0 + jnp.arange(m_local)
        deltas = jax.vmap(
            lambda k, x, y: sample_class_delta(
                tm_cfg, state_l, m_ids, k, x, y
            )
        )(keys, xb_l.astype(jnp.bool_), yb_l)
        delta = jnp.sum(deltas, axis=0)
        if bx:
            delta = jax.lax.psum(delta, bx)
        return jnp.clip(state_l + delta, 1, 2 * N)

    def step(state, key, xb, yb):
        return shard_map(
            local,
            mesh=mesh,
            in_specs=(state_spec, P(), P(bx, None), P(bx)),
            out_specs=state_spec,
        )(state, key, xb, yb)

    return jax.jit(step)


def make_prefill_step(cfg) -> Callable:
    """-> step(params, batch) -> (last-position logits, kv cache)."""
    fam = family_for(cfg)

    def step(params, batch):
        return fam.prefill(cfg, params, batch)

    return step


def make_decode_step(cfg) -> Callable:
    """-> step(params, cache, batch) -> (greedy token int32[B], cache).

    Greedy sampling lives inside the compiled program so the serving loop
    moves one int per sequence per step off-device, not the logits.
    """
    fam = family_for(cfg)

    def step(params, cache, batch):
        logits, cache = fam.decode(cfg, params, cache, batch)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return step
