"""Mixture-of-Experts FFN (top-k routing, sort+scatter dispatch, EP-shardable).

Dispatch strategy (compile-friendly on any backend, EP-sharded on the
``model`` mesh axis):
  1. router logits -> top-k experts per token (fp32 router)
  2. assignments sorted by expert id; rank-within-expert via searchsorted
  3. tokens scattered into a capacity-bounded [E, C, D] buffer
     (assignments past capacity C are dropped, standard GShard semantics)
  4. per-expert SwiGLU via batched einsum on the [E, ...] buffers
  5. results gathered back and combined with router weights

The [E, C, D] buffers and [E, D, F] weights shard on E over the ``model``
axis; XLA inserts the all-to-all at the scatter/gather boundaries.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .common import sds

Array = jax.Array


def moe_param_specs(cfg: ArchConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": sds((D, E), jnp.float32),
        "w_gate": sds((E, D, F), dtype),
        "w_up": sds((E, D, F), dtype),
        "w_down": sds((E, F, D), dtype),
    }


def moe_capacity(cfg: ArchConfig, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8 for tiling


def _dispatch_compute(xf, logits, w_gate, w_up, w_down, *, k, n_experts, C, dtype):
    """Capacity-bounded top-k dispatch + per-expert SwiGLU + combine.

    xf: [T, D]; logits fp32 [T, E_total]; weights [E_local, D, F].
    Experts outside [expert_lo, expert_lo + E_local) are dropped (their
    contribution comes from other shards; see moe_ffn_ep)."""
    T, D = xf.shape
    E_local = w_gate.shape[0]
    topw, topi = jax.lax.top_k(logits, k)  # [T, k] (global expert ids)
    topw = jax.nn.softmax(topw, axis=-1).astype(dtype)

    flat_e = topi.reshape(-1)  # [T*k]
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = topw.reshape(-1)
    # local assignments keep id in [0, E_local); others -> sink E_local
    local = (flat_e >= 0) & (flat_e < E_local)
    flat_e = jnp.where(local, flat_e, E_local)

    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(T * k) - first  # rank within expert
    keep = (pos < C) & (se < E_local)
    pos_c = jnp.where(keep, pos, 0)
    se_c = jnp.where(keep, se, 0)

    buf = jnp.zeros((E_local, C, D), dtype)
    buf = buf.at[se_c, pos_c].add(jnp.where(keep[:, None], xf[st], 0))

    h_g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    h_u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = jax.nn.silu(h_g) * h_u
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)  # [E_local, C, D]

    vals = out_buf[se_c, pos_c] * jnp.where(keep, sw, 0)[:, None]
    return jnp.zeros((T, D), dtype).at[st].add(vals)


def moe_ffn(p: Dict[str, Array], x: Array, cfg: ArchConfig) -> Array:
    """x: [B, S, D] -> [B, S, D].  Uses the shard_map expert-parallel path
    when an activation mesh is installed (EP: experts local, one psum of
    [T_local, D] per layer — see EXPERIMENTS.md §Perf); otherwise the plain
    single-device path."""
    from ..dist.sharding import _ACTIVATION_MESH

    mesh = _ACTIVATION_MESH
    if (
        mesh is not None
        and "model" in mesh.axis_names
        and cfg.n_experts % dict(zip(mesh.axis_names, mesh.devices.shape))["model"] == 0
    ):
        return moe_ffn_ep(p, x, cfg, mesh)
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    y = _dispatch_compute(
        xf, logits, p["w_gate"], p["w_up"], p["w_down"],
        k=cfg.top_k, n_experts=cfg.n_experts,
        C=moe_capacity(cfg, T), dtype=x.dtype,
    )
    return y.reshape(B, S, D)


def moe_ffn_ep(p: Dict[str, Array], x: Array, cfg: ArchConfig, mesh) -> Array:
    """Expert-parallel MoE via shard_map (beyond-paper optimization).

    Tokens shard over (pod, data); experts shard over model.  Each device
    routes its token block to its LOCAL experts only and the partial outputs
    are summed with one psum over ``model`` — replacing the GSPMD
    replicate+all-reduce of the [E, C, D] dispatch buffer (which dominated
    the baseline collective term) with a [T_local, D] reduction."""
    from jax.sharding import PartitionSpec as P

    from ..dist.sharding import batch_axes, shard_map

    B, S, D = x.shape
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes["model"]
    bx = batch_axes(mesh, B)
    n_batch = 1
    if bx:
        import numpy as _np

        n_batch = int(_np.prod([sizes[a] for a in bx]))
    T_local = (B // n_batch) * S
    C = moe_capacity(cfg, T_local)
    E_local = cfg.n_experts // n_model

    def local_fn(xl, router, wg, wu, wd):
        # xl: [B_l, S, D]; wg: [E_local, D, F]
        B_l = xl.shape[0]
        xf = xl.reshape(B_l * S, D)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
        lo = jax.lax.axis_index("model") * E_local
        # route against the GLOBAL router, then localize expert ids
        topw, topi = jax.lax.top_k(logits, cfg.top_k)
        topw = jax.nn.softmax(topw, axis=-1).astype(xl.dtype)
        e_loc = topi - lo
        T = B_l * S
        flat_e = e_loc.reshape(-1)
        local = (flat_e >= 0) & (flat_e < E_local)
        flat_e = jnp.where(local, flat_e, E_local)
        flat_t = jnp.repeat(jnp.arange(T), cfg.top_k)
        flat_w = topw.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sw = flat_e[order], flat_t[order], flat_w[order]
        first = jnp.searchsorted(se, se, side="left")
        pos = jnp.arange(T * cfg.top_k) - first
        keep = (pos < C) & (se < E_local)
        pos_c = jnp.where(keep, pos, 0)
        se_c = jnp.where(keep, se, 0)
        buf = jnp.zeros((E_local, C, D), xl.dtype)
        buf = buf.at[se_c, pos_c].add(jnp.where(keep[:, None], xf[st], 0))
        h_g = jnp.einsum("ecd,edf->ecf", buf, wg)
        h_u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jax.nn.silu(h_g) * h_u
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd)
        vals = out_buf[se_c, pos_c] * jnp.where(keep, sw, 0)[:, None]
        yl = jnp.zeros((T, D), xl.dtype).at[st].add(vals)
        yl = jax.lax.psum(yl, "model")  # combine expert shards
        return yl.reshape(B_l, S, D)

    x_spec = P(bx, None, None)
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            x_spec,
            P(),  # router replicated
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=x_spec,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
