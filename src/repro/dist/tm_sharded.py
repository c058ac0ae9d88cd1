"""Mesh-sharded compressed-TM inference: the paper's multi-core class-split
(Fig 7) realized as a JAX ``shard_map`` over a (data, model) mesh.

Layout (the MATADOR-style plan: one fixed layout chosen per deployment,
exploited ETHEREAL-style by the compressed include-list executors):

  * classes shard over ``model``  — each device holds the include plans of
    its class slice only (the AXIS splitter of core/runtime.py, mesh-native)
  * the batch shards over every non-model axis (``sharding.batch_axes``)
  * each device runs a *local plan executor* over its (class, batch) tile;
    the combined output is the global [B, M] class-sum matrix with no
    collective at all (outputs tile disjointly).

Three local executors over decode_to_plan output, all bit-exact against
``core.batch_class_sums`` (enforced by tests/test_tm_sharded.py):

  _local_plan_executor             include-major streaming over CHUNK-sized
                                   instruction blocks, scatter-min clause
                                   accumulation (clauses may span chunks)
  _local_plan_executor_packed      the same stream over pack_literals words
                                   (32 datapoints per uint32, paper §3),
                                   running-AND with seg_last emission
  _local_plan_executor_clausemajor clause-major padded include table, one
                                   gather + AND-reduce per clause (the
                                   TPU-native layout build_tm_sharded uses)
"""

from __future__ import annotations

import dataclasses
import json
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import _pad_to
from ..core.tm import unpack_bits
from .sharding import _axis_sizes, batch_axes, shard_map

# Includes processed per streaming step of the include-major executors
# (the VMEM-resident instruction block; tests shrink it to force
# chunk-spanning clauses).
CHUNK = 512

_ONES32 = 0xFFFFFFFF


# ---------------------------------------------------------------------------
# local (single-shard) plan executors
# ---------------------------------------------------------------------------

def _local_plan_executor(lit_idx, cid, clause_class, clause_pol, lits):
    """Include-major executor over an unpacked literal matrix.

    lit_idx      int32[I_cap]  absolute literal slots, padded with 0
    cid          int32[I_cap]  global clause id; padded slots -> NCL (sink)
    clause_class int32[NCL]    class of each clause
    clause_pol   int32[NCL]    +1 / -1
    lits         {0,1}[B, 2F]  interleaved literal matrix
    -> int32[NCL, B] class sums (rows >= n_classes are zero; caller slices)

    Streams the include list in CHUNK-sized blocks; each block scatter-mins
    into a clause accumulator, so clauses spanning block boundaries combine
    correctly.  Clauses that never receive an include output 0 (inference
    semantics for empty clauses).
    """
    B = lits.shape[0]
    NCL = clause_pol.shape[0]
    I_cap = lit_idx.shape[0]
    chunk = min(CHUNK, I_cap)
    assert I_cap % chunk == 0, (I_cap, chunk)
    n_chunks = I_cap // chunk

    sel = jnp.take(lits.astype(jnp.int32).T, lit_idx, axis=0)  # [I_cap, B]
    sel_c = sel.reshape(n_chunks, chunk, B)
    cid_c = cid.reshape(n_chunks, chunk)

    def body(carry, inp):
        acc, cnt = carry
        s, c = inp  # s: [chunk, B]; c: [chunk]
        acc = acc.at[c].min(s)
        cnt = cnt.at[c].add(1)
        return (acc, cnt), None

    acc0 = jnp.ones((NCL + 1, B), jnp.int32)  # +1: sink row for padding
    cnt0 = jnp.zeros((NCL + 1,), jnp.int32)
    (acc, cnt), _ = jax.lax.scan(body, (acc0, cnt0), (sel_c, cid_c))

    clause_out = jnp.where(cnt[:NCL, None] > 0, acc[:NCL], 0)  # [NCL, B]
    contrib = clause_out * clause_pol[:, None]
    return jnp.zeros((NCL, B), jnp.int32).at[clause_class].add(contrib)


def _local_plan_executor_packed(lit_idx, seg_last, clause_class, clause_pol,
                                packed):
    """Include-major executor over pack_literals words (32 points/word).

    lit_idx   int32[I_cap]   absolute literal slots, padded with 0
    seg_last  int32[I_cap]   1 at the last include of each clause, else 0
    packed    uint32[2F, W]  pack_literals output (bit b = datapoint w*32+b)
    -> int32[NCL, W*32] class sums

    A running AND word accumulates the current clause; on seg_last the word
    is emitted to the clause's output row and the accumulator resets.  The
    instruction stream is consumed in CHUNK-sized blocks (outer scan) with
    a sequential inner scan — the same fetch/accumulate discipline as the
    eFPGA pipeline, 32-wide.
    """
    NCL = clause_pol.shape[0]
    W = packed.shape[1]
    ones = jnp.uint32(_ONES32)
    I_cap = lit_idx.shape[0]
    chunk = min(CHUNK, I_cap)
    assert I_cap % chunk == 0, (I_cap, chunk)
    n_chunks = I_cap // chunk

    words = jnp.take(packed, lit_idx, axis=0)  # [I_cap, W]
    words_c = words.reshape(n_chunks, chunk, W)
    last_c = seg_last.reshape(n_chunks, chunk)

    def instr(carry, inp):
        acc, c, out = carry
        w, last = inp  # w: [W]; last: scalar
        acc = acc & w
        row = jnp.where(last == 1, c, NCL)  # non-final writes hit the sink
        out = out.at[row].set(acc)
        c = c + last
        acc = jnp.where(last == 1, ones, acc)
        return (acc, c, out), None

    def chunk_body(carry, inp):
        carry, _ = jax.lax.scan(instr, carry, inp)
        return carry, None

    out0 = jnp.zeros((NCL + 1, W), jnp.uint32)
    carry0 = (jnp.full((W,), ones, jnp.uint32), jnp.int32(0), out0)
    (_, _, out), _ = jax.lax.scan(chunk_body, carry0, (words_c, last_c))

    bits = unpack_bits(out[:NCL])  # [NCL, W*32]
    contrib = bits * clause_pol[:, None]
    return jnp.zeros((NCL, W * 32), jnp.int32).at[clause_class].add(contrib)


def _local_plan_executor_clausemajor(pad_idx, clause_class, clause_pol,
                                     packed1):
    """Clause-major executor: padded include table, bitpacked datapoints.

    pad_idx  int32[NCL, Lc]   per-clause literal slots, padded with the
                              index of the all-ones row of ``packed1``
    packed1  uint32[2F+1, W]  pack_literals output + one all-ones row
    -> int32[NCL, W*32] class sums

    One gather + one AND-reduction per clause — fully parallel over clauses
    AND datapoints (this is the layout ``build_tm_sharded`` distributes).
    """
    ones = jnp.uint32(_ONES32)
    words = jnp.take(packed1, pad_idx, axis=0)  # [NCL, Lc, W]
    acc = jax.lax.reduce(words, ones, jnp.bitwise_and, dimensions=(1,))
    bits = unpack_bits(acc)  # [NCL, W*32]
    contrib = bits * clause_pol[:, None]
    return jnp.zeros_like(contrib).at[clause_class].add(contrib)


# ---------------------------------------------------------------------------
# sharded executor
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TMShardedConfig:
    """A deployed multi-core TM: model dims + executor capacity plan."""

    name: str
    n_classes: int
    n_clauses: int      # clauses per class
    n_features: int
    batch: int          # global batch (multiple of 32: bitpacked words)
    include_cap: int = 0  # max includes per clause (0 -> density estimate)
    density: float = 0.05

    @property
    def lc_cap(self) -> int:
        if self.include_cap:
            return self.include_cap
        est = int(2 * self.n_features * self.density * 2)
        return max(8, -(-est // 8) * 8)


TM_CONFIGS: Dict[str, TMShardedConfig] = {
    # the paper's MNIST-scale machine, batch-scaled for mesh serving
    "tm-paper": TMShardedConfig(
        name="tm-paper", n_classes=10, n_clauses=128, n_features=784,
        batch=8192, density=0.05,
    ),
    "tm-xl": TMShardedConfig(
        name="tm-xl", n_classes=64, n_clauses=512, n_features=4096,
        batch=32768, density=0.02,
    ),
}


def build_tm_sharded(cfg: TMShardedConfig, mesh) -> Tuple[Callable, tuple]:
    """-> (fn, specs): the jittable class x batch sharded executor.

    fn(idx, pol, lits) -> int32[Bp, Mp] class sums, where
      idx  int32[Mp, C, Lc]  per-class clause-major include tables (padded
                             entries point at the trailing all-ones column)
      pol  int32[Mp, C]      +1/-1, 0 for padded clauses/classes
      lits int8[Bp, 2F+1]    interleaved literals + all-ones pad column

    Classes shard over ``model`` (Mp is padded up to divide), the batch over
    the non-model axes; each device computes its disjoint [B_l, M_l] tile so
    the assembled output needs no collective.  ``specs`` are ShapeDtypeStructs
    carrying the input NamedShardings — pass them straight to
    ``jax.jit(fn).lower(*specs)`` (dry-run) or build real operands with
    ``operands_from_plan``.
    """
    sizes = _axis_sizes(mesh)
    n_model = sizes.get("model", 1)
    Mp = _pad_to(cfg.n_classes, n_model)
    Bp = cfg.batch
    C, Lc, F2 = cfg.n_clauses, cfg.lc_cap, 2 * cfg.n_features
    bx = batch_axes(mesh, Bp)

    idx_spec = P("model", None, None)
    pol_spec = P("model", None)
    lit_spec = P(bx, None)
    out_spec = P(bx, "model")

    def local(idx_l, pol_l, lits_l):
        # idx_l: [M_l, C, Lc]; lits_l: [B_l, 2F+1]
        sel = jnp.take(lits_l.astype(jnp.int32), idx_l, axis=1)
        clause = jnp.min(sel, axis=-1)          # [B_l, M_l, C] AND of includes
        return jnp.sum(clause * pol_l[None].astype(jnp.int32), axis=-1)

    def fn(idx, pol, lits):
        return shard_map(
            local, mesh=mesh,
            in_specs=(idx_spec, pol_spec, lit_spec),
            out_specs=out_spec,
        )(idx, pol, lits)

    specs = (
        jax.ShapeDtypeStruct((Mp, C, Lc), jnp.int32,
                             sharding=NamedSharding(mesh, idx_spec)),
        jax.ShapeDtypeStruct((Mp, C), jnp.int32,
                             sharding=NamedSharding(mesh, pol_spec)),
        jax.ShapeDtypeStruct((Bp, F2 + 1), jnp.int8,
                             sharding=NamedSharding(mesh, lit_spec)),
    )
    return fn, specs


def fill_clause_tables(plan, Mp: int, C: int, Lc: int, F2: int):
    """DecodedPlan -> clause-major (idx int32[Mp, C, Lc], pol int32[Mp, C]).

    Padded idx entries point at the all-ones literal column ``F2``; padded
    pol entries are 0 so they contribute nothing.  Clause weights
    (repro.prune) fold straight into the polarity table
    (``pol = weight * polarity``) — the local executor's
    ``clause * pol`` sum is already a weighted vote, so weighted models
    run the SAME compiled shard_map, bit-identical at weight 1.  Raises
    when the plan exceeds the (C, Lc) capacity plan (the mesh analog of
    "resynthesize with a bigger AcceleratorConfig").  Shared by
    ``operands_from_plan`` and the serve_tm sharded executor.
    """
    idx = np.full((Mp, C, Lc), F2, np.int32)
    pol = np.zeros((Mp, C), np.int32)
    next_slot = np.zeros(Mp, np.int64)
    wpol = plan.weighted_pol
    # clause_id is sorted (decode_to_plan emits stream order), so one
    # searchsorted gives every clause's include span.
    bounds = np.searchsorted(
        plan.clause_id, np.arange(plan.n_clauses_total + 1)
    )
    for c in range(plan.n_clauses_total):
        m = int(plan.clause_class[c])
        j = int(next_slot[m])
        next_slot[m] += 1
        if j >= C:
            raise ValueError(f"class {m} exceeds clause capacity {C}")
        ks = plan.lit_idx[bounds[c] : bounds[c + 1]]
        if ks.size > Lc:
            raise ValueError(
                f"clause {c} has {ks.size} includes; capacity {Lc}"
            )
        idx[m, j, : ks.size] = ks
        pol[m, j] = int(wpol[c])
    return idx, pol


def operands_from_plan(cfg: TMShardedConfig, plan, X: np.ndarray, mesh):
    """DecodedPlan + raw features -> real operands matching build_tm_sharded.

    Raises if the plan exceeds the config's capacity plan (the mesh analog
    of "resynthesize with a bigger AcceleratorConfig").
    """
    from ..core.tm import literals

    Mp = _pad_to(cfg.n_classes, _axis_sizes(mesh).get("model", 1))
    C, Lc, F2 = cfg.n_clauses, cfg.lc_cap, 2 * cfg.n_features
    idx, pol = fill_clause_tables(plan, Mp, C, Lc, F2)

    B = X.shape[0]
    if B != cfg.batch:
        raise ValueError(f"batch {B} != configured {cfg.batch}")
    lits = np.asarray(literals(jnp.asarray(X, bool))).astype(np.int8)
    lits1 = np.concatenate([lits, np.ones((B, 1), np.int8)], axis=1)
    return jnp.asarray(idx), jnp.asarray(pol), jnp.asarray(lits1)


def dryrun_tm(name: str, *, multi_pod: bool = False, out_dir=None) -> dict:
    """Lower + compile the sharded TM on the production mesh and derive
    roofline terms (the --include-tm path of launch/dryrun.py)."""
    from ..analysis.roofline import build_roofline
    from ..launch.mesh import make_production_mesh

    cfg = TM_CONFIGS[name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    fn, specs = build_tm_sharded(cfg, mesh)
    with mesh:
        compiled = jax.jit(fn).lower(*specs).compile()
    cost = compiled.cost_analysis()
    # useful work: one AND + one accumulate per (include, datapoint)
    includes = cfg.n_classes * cfg.n_clauses * cfg.lc_cap
    mf = 2.0 * includes * cfg.batch
    rl = build_roofline(
        arch=name, shape=f"batch{cfg.batch}", mesh_name=mesh_name,
        chips=mesh.devices.size, cost=cost, hlo_text=compiled.as_text(),
        model_flops_global=mf,
    )
    rec = json.loads(rl.to_json())
    if out_dir is not None:
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{name}_{mesh_name}.json").write_text(
            json.dumps(rec, indent=1)
        )
    return rec
