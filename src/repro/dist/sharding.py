"""Sharding rules: the single source of truth for how params, optimizer
state, activations, inputs and KV caches are laid out on a mesh.

Mesh axes (launch/mesh.py):
  pod    cross-pod data parallelism (DCN)           -- optional, 3-axis only
  data   in-pod data parallelism / FSDP
  model  tensor / expert / vocab parallelism

The batch dimension shards over every non-``model`` axis that divides it
(``batch_axes``); weight matrices shard their largest contraction-free dim
over ``model`` and (under FSDP) a second dim over ``data``; anything that
does not divide evenly stays replicated — the rules never raise on a
degenerate mesh, so the same code paths run from a 1-chip CI box to the
2x16x16 production mesh.

Activation hints (``hint``) are advisory ``with_sharding_constraint``s: the
model code states the logical layout ("batch", None, "model") and this
module translates it for whatever mesh is installed (or is a no-op when
none is).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Installed by set_activation_mesh; read by hint() and the MoE EP gate.
_ACTIVATION_MESH: Optional[Mesh] = None


def set_activation_mesh(mesh: Optional[Mesh]) -> None:
    """Install (or clear, with None) the mesh used by activation hints."""
    global _ACTIVATION_MESH
    _ACTIVATION_MESH = mesh


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_mesh(axis_shapes, axis_names) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    The rules in this module place arrays through ``NamedSharding`` and
    ``with_sharding_constraint`` and let the partitioner propagate the
    rest; ``jax.make_mesh`` defaults to ``Explicit`` axes, which refuse
    such constraints and put shardings into array types.  Every mesh the
    repo builds comes from here."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(AxisType.Auto,) * len(axis_names)
    )


def shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off.

    The TM executors and the MoE expert block write per-device outputs
    that the check cannot prove (``psum`` over a subset of axes, outputs
    that tile disjointly), so every caller in the repo turns it off; this
    is the one place that says so."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def batch_axes(mesh: Mesh, B: int) -> Optional[Tuple[str, ...]]:
    """Mesh axes the batch dim shards over, major-to-minor.

    Every non-``model`` axis is taken in mesh order while the running
    product still divides ``B`` — so a (pod, data, model) mesh yields
    ("pod", "data"), a (data, model) mesh yields ("data",), and a batch
    too small for the leading axis stays replicated (None).
    """
    sizes = _axis_sizes(mesh)
    chosen = []
    prod = 1
    for name in mesh.axis_names:
        if name == "model":
            continue
        if B % (prod * sizes[name]) == 0:
            chosen.append(name)
            prod *= sizes[name]
        else:
            break
    return tuple(chosen) if chosen else None


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def hint(x: jax.Array, *axes: Any) -> jax.Array:
    """Advisory activation layout: one entry per leading dim of ``x``.

    Entries: "batch" (shard over batch_axes), a mesh axis name, or None.
    No-op when no activation mesh is installed or a dim does not divide.
    """
    mesh = _ACTIVATION_MESH
    if mesh is None:
        return x
    sizes = _axis_sizes(mesh)
    spec = []
    for d, a in enumerate(axes):
        if a is None:
            spec.append(None)
        elif a == "batch":
            spec.append(batch_axes(mesh, x.shape[d]))
        elif a in sizes and x.shape[d] % sizes[a] == 0:
            spec.append(a)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


# ---------------------------------------------------------------------------
# parameter shardings
# ---------------------------------------------------------------------------

def _path_names(path) -> list:
    out = []
    for p in path:
        k = getattr(p, "key", None)
        if k is None:
            k = getattr(p, "name", None)
        if k is None:
            k = getattr(p, "idx", None)
        out.append(str(k))
    return out


def _param_spec(cfg, mesh, path, leaf) -> P:
    """One PartitionSpec per param leaf.

    Rules (checked in this order):
      * scalars / vectors (norm scales)            -> replicated
      * embedding [V, D]                           -> vocab over model
                                                      (+ D over data if fsdp)
      * router [D, E]                              -> replicated (fp32, tiny)
      * MoE expert stacks [L, E, D, F]             -> experts over model (EP)
      * attention weights with cfg.attn_tp=False   -> replicated (pure DP)
      * other matrices: largest non-stack dim over model; under FSDP the
        largest remaining dim over data.  A dim is only assigned an axis
        it divides evenly; otherwise it stays replicated.
    """
    sizes = _axis_sizes(mesh)
    n_model = sizes.get("model", 1)
    n_data = sizes.get("data", 1)
    names = _path_names(path)
    shape = leaf.shape
    spec = [None] * len(shape)

    if len(shape) <= 1:
        return P()

    if "embed" in names:
        if "model" in sizes and shape[0] % n_model == 0:
            spec[0] = "model"
        if cfg.fsdp and "data" in sizes and shape[1] % n_data == 0:
            spec[1] = "data"
        return P(*spec)

    if "router" in names:
        return P(*spec)

    is_attn = any(n in ("attn", "wq", "wk", "wv", "wo", "self_attn",
                        "cross_attn") for n in names)
    if is_attn and not cfg.attn_tp:
        return P(*spec)

    is_expert = cfg.is_moe and any(
        n in ("w_gate", "w_up", "w_down") for n in names
    ) and "moe" in names
    if is_expert:
        # [L, E, D, F] (stacked) or [E, D, F]: shard the expert dim
        e_dim = 1 if len(shape) == 4 else 0
        if "model" in sizes and shape[e_dim] % n_model == 0:
            spec[e_dim] = "model"
        return P(*spec)

    # generic matrix: dims after the leading stack dim are candidates;
    # for unstacked 2-D weights all dims are candidates.
    cand = list(range(1, len(shape))) if len(shape) >= 3 else list(range(len(shape)))
    by_size = sorted(cand, key=lambda d: shape[d], reverse=True)
    for d in by_size:
        if "model" in sizes and shape[d] % n_model == 0:
            spec[d] = "model"
            break
    if cfg.fsdp and "data" in sizes:
        for d in by_size:
            if spec[d] is None and shape[d] % n_data == 0:
                spec[d] = "data"
                break
    return P(*spec)


def param_shardings(cfg, mesh: Mesh, specs: Any) -> Any:
    """Param-spec pytree -> NamedSharding pytree (one sharding per leaf)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, _param_spec(cfg, mesh, path, leaf)),
        specs,
    )


def opt_shardings(cfg, mesh: Mesh, o_specs: Any, p_sh: Any) -> Any:
    """AdamW state shards exactly like the params; step is replicated."""
    from ..optim.adamw import AdamWState

    return AdamWState(step=replicated(mesh), m=p_sh, v=p_sh)


# ---------------------------------------------------------------------------
# input / cache shardings
# ---------------------------------------------------------------------------

def input_shardings(cfg, mesh: Mesh, shape, in_specs: Any) -> Any:
    """Batch-leading inputs shard over the batch axes; scalars replicate."""
    bx = batch_axes(mesh, shape.global_batch)

    def rule(leaf):
        if leaf.ndim >= 1 and leaf.shape[0] == shape.global_batch:
            return NamedSharding(mesh, P(bx, *([None] * (leaf.ndim - 1))))
        return replicated(mesh)

    return jax.tree.map(rule, in_specs)


def _cache_head_sizes(cfg) -> set:
    """Every head count a decode-cache dim of this config might carry:
    attention heads (q and kv) plus, for the SSM/recurrent families, the
    SSM head count (xLSTM's mLSTM head count IS ``n_heads``)."""
    heads = set()
    for attr in ("n_heads", "n_kv_heads"):
        v = getattr(cfg, attr, None)
        if v:
            heads.add(int(v))
    if getattr(cfg, "family", "") in ("ssm_xlstm", "hybrid"):
        from ..models.ssm import ssm_dims  # deferred: models import dist

        heads.add(ssm_dims(cfg)[1])
    return heads


def cache_shardings(cfg, mesh: Mesh, shape, c_specs: Any) -> Any:
    """Decode caches shard their batch dim over the batch axes and their
    HEAD dim over model — for every cache family, not just attention KV:

      KV          [L, B, S, H, hd]       head at dim 3
      SSM conv    [L, B, K-1, d_conv]    batch only (channel mix, no heads)
      SSM state   [L, B, H, N, P]        head at dim 2
      hybrid SSM  [G, E, B, H, N, P]     batch at dim 2, head at dim 3
      mLSTM C/n/m [P, B, H, hd, hd] / [P, B, H, hd] / [P, B, H]
                                         head at dim 2
      sLSTM       [P, B, D]              batch only (fused per-channel)

    The head dim is recognized by its SIZE (one of the config's head
    counts, see ``_cache_head_sizes``): the first such dim after the
    batch dim takes "model", except the KV convention [stack, B, S, H,
    hd] which pins dim 3 so a window length colliding with a head count
    cannot steal the assignment.  The pin checks the shape signature,
    not just rank: the mLSTM C cache [P, B, H, hd, hd] is also 5-D and
    its per-head feature dim 3 coincides with a head count whenever
    hd == H (e.g. d_model=64, n_heads=8) — a square trailing [hd, hd]
    with a head count at dim 2 is recognized as that matrix-memory
    signature and falls through to the generic first-head-after-batch
    rule (dim 2), as the table above requires.  Dims that don't divide
    the axis stay replicated, as everywhere in this module."""
    sizes = _axis_sizes(mesh)
    n_model = sizes.get("model", 1)
    bx = batch_axes(mesh, shape.global_batch)
    heads = _cache_head_sizes(cfg)

    def rule(leaf):
        spec = [None] * leaf.ndim
        # caches are [stack, B, ...] (dim 1), prefill-less [B, ...], or
        # double-stacked hybrid groups [G, E, B, ...] (dim 2)
        b_dim = next(
            (
                d
                for d in (1, 0, 2)
                if d < leaf.ndim and leaf.shape[d] == shape.global_batch
            ),
            None,
        )
        if b_dim is not None:
            spec[b_dim] = bx
        if "model" in sizes:
            def head_at(d):
                return leaf.shape[d] in heads and leaf.shape[d] % n_model == 0

            is_mlstm_c = (
                leaf.ndim == 5
                and leaf.shape[3] == leaf.shape[4]
                and leaf.shape[2] in heads
            )
            if (leaf.ndim == 5 and b_dim == 1 and head_at(3)
                    and not is_mlstm_c):
                spec[3] = "model"  # the KV [L, B, S, H, hd] convention
            else:
                for d in range((b_dim if b_dim is not None else -1) + 1,
                               leaf.ndim):
                    if spec[d] is None and head_at(d):
                        spec[d] = "model"
                        break
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(rule, c_specs)
