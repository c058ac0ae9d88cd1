"""The four built-in engine plugins: interp / plan / sharded / popcount.

One ``CompressedModel`` contract, four realizations (all bit-exact
against the ``core.tm.batch_class_sums`` oracle — enforced by
tests/test_serve_tm.py and tests/test_accel.py):

  * ``interp``   — the paper-faithful stream interpreter
    (``core.interp.interpret_stream``): one instruction per scan step over
    the fixed-depth instruction memory.
  * ``plan``     — the decoded-plan fast path
    (``core.interp.plan_class_sums``): gather + segmented reduction,
    parallel across includes and datapoints.
  * ``sharded``  — the ``dist.tm_sharded`` clause-major shard_map executor
    (classes over ``model``, batch over the data axes); on a 1x1 mesh this
    is the single-device realization of the Fig-7 multi-core split.
    Takes the mesh as a per-engine option (``needs_mesh`` capability).
  * ``popcount`` — the popcount bitplane fast path
    (``kernels.tm_popcount``): clause outputs stay packed ``uint32`` until
    a clause boundary; class sums come from ``lax.population_count``
    against per-class polarity-bank selection bitplanes.  Pallas kernel on
    TPU, the bit-exact pure-XLA twin elsewhere (``implementation``
    option); donates its per-call staging copy (``supports_donation``).
    The only engine that evaluates patch positions (a convolutional TM):
    the others refuse such a model (``EngineBase.validate_model``).

Every engine instance owns a PRIVATE jit cache (a fresh closure over the
underlying function), so ``compile_cache_size()`` counts only this
engine's compilations.  Serving buffers are device-resident: ``program()``
moves the decoded program to the accelerator ONCE (``jax.device_put``);
per-flush features are packed by the batcher straight into the
preallocated host staging array (``EngineBase.staging``).

Capacity validation is uniform (``EngineBase.program`` runs
``plan.validate`` first), so the per-engine ``_program`` bodies are pure
decode + data movement.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..configs.base import _pad_to
from ..core.compress import CompressedModel, decode_to_plan
from ..core.interp import interpret_stream, pack_features, pad_plan, plan_class_sums
from ..core.tm import (
    PatchGeometry,
    literals,
    pack_literals,
    pack_patch_literals,
)
from ..dist.sharding import _axis_sizes, make_mesh
from ..dist.tm_sharded import (
    TMShardedConfig,
    build_tm_sharded,
    fill_clause_tables,
)
from ..kernels.tm_popcount.kernel import (
    kernel_blocks,
    kernel_operands,
    sum_weight_planes,
    tm_popcount_resident,
    tm_popcount_xla,
)
from ..kernels.tm_popcount.ops import patch_operands, plan_to_popcount_operands
from .capacity import CapacityExceeded, CapacityPlan
from .engine import EngineBase, _private_jit, register_engine
from .spans import D2H, H2D, LAUNCH


@register_engine("interp", priority=10)
class InterpEngine(EngineBase):
    """Paper-faithful fixed-capacity stream interpreter (Fig 4.4-4.6)."""

    validated_knobs = (
        "instruction_capacity", "feature_capacity", "class_capacity",
        "input_capacity",
    )

    def __init__(self, plan: CapacityPlan):
        super().__init__(plan)
        self._fn = _private_jit(
            interpret_stream.__wrapped__, "tm_interp_step",
            static_argnames=("m_cap",),
        )

    def _program(self, model: CompressedModel, decoded=None) -> Dict[str, Any]:
        p = self.plan
        imem = np.zeros(p.instruction_capacity, np.uint16)
        imem[: model.n_instructions] = model.instructions
        # per-clause weight memory, indexed by the interpreter's finalize
        # ordinal (non-empty clauses in emission order).  Always present at
        # instruction-capacity depth (a clause needs >= 1 instruction, so
        # it can never be too small) and all-ones for weightless models:
        # one operand signature -> one compiled program across weighted and
        # weightless swaps.
        wmem = np.ones(p.instruction_capacity, np.int32)
        if model.clause_weights is not None:
            wmem[: model.n_weights] = model.clause_weights
        return {
            "imem": jnp.asarray(imem),
            "wmem": jnp.asarray(wmem),
            "n_inst": jnp.int32(model.n_instructions),
            "n_classes": model.n_classes,
            "n_features": model.n_features,
        }

    def class_sums(self, prog: Dict[str, Any], x: np.ndarray) -> np.ndarray:
        p = self.plan
        B = x.shape[0]
        packed = pack_features(
            jnp.asarray(self._pad_features(x)), p.feature_capacity,
            p.batch_words,
        )
        sums = self._fn(
            prog["imem"], prog["n_inst"], packed, jnp.int32(B), prog["wmem"],
            m_cap=p.class_capacity,
        )
        return np.asarray(sums)[: prog["n_classes"], :B].T


@register_engine("plan", priority=20)
class PlanEngine(EngineBase):
    """Decoded-plan engine: gather + segmented min/sum (beyond-paper)."""

    # clause_capacity bounds the segment table: per-class max clauses <=
    # clause_capacity (with n_classes <= class_capacity) implies
    # n_clauses_total <= clause_total_capacity, so a model that passes
    # compile-time validation can never blow the load-path table fill.
    # instruction_capacity bounds the include operand vectors only —
    # boundary EXTENDs never materialize in the decoded plan
    validated_knobs = (
        "instruction_capacity", "feature_capacity", "class_capacity",
        "clause_capacity", "input_capacity",
    )
    instruction_metric = "includes"
    needs_decoded_plan = True

    def __init__(self, plan: CapacityPlan):
        super().__init__(plan)
        self._fn = _private_jit(
            plan_class_sums.__wrapped__, "tm_plan_step",
            static_argnames=("n_clause_cap", "m_cap"),
        )

    def _program(self, model: CompressedModel, decoded=None) -> Dict[str, Any]:
        p = self.plan
        plan = decoded if decoded is not None else decode_to_plan(model)
        if plan.n_clauses_total > p.clause_total_capacity:
            # unreachable after validation; kept as a corruption guard on
            # the class_cap*clause_cap-deep segment table
            raise CapacityExceeded(
                "clause_capacity",
                -(-plan.n_clauses_total // p.class_capacity),
                p.clause_capacity,
                "total clauses",
            )
        li, ci, cc, cp = pad_plan(
            plan, p.instruction_capacity, p.clause_total_capacity
        )
        return {
            "li": jnp.asarray(li), "ci": jnp.asarray(ci),
            "cc": jnp.asarray(cc), "cp": jnp.asarray(cp),
            "n_classes": model.n_classes,
            "n_features": model.n_features,
        }

    def class_sums(self, prog: Dict[str, Any], x: np.ndarray) -> np.ndarray:
        p = self.plan
        B = x.shape[0]
        lits = literals(jnp.asarray(self._pad_features(x)))  # [B_cap, 2*F_cap]
        sums = self._fn(
            prog["li"], prog["ci"], prog["cc"], prog["cp"], lits,
            n_clause_cap=p.clause_total_capacity, m_cap=p.class_capacity,
        )
        return np.asarray(sums)[:B, : prog["n_classes"]]


def _packed_literals(x_staged, patch):
    """Staged rows -> the kernel's packed literal panel: ``[2F, W]`` of a
    plain TM's features, or with the program's patch operands
    (``sources``, ``valid``) ``[2F, P, W]`` of every patch of the staged
    images, formed on the device inside the same step."""
    if not patch:
        return pack_literals(x_staged)
    return pack_patch_literals(x_staged, *patch)


def _popcount_engine_xla(lit_idx, last, mask_pos, mask_neg, x_staged, *patch):
    """Staged rows -> packed interleaved literals -> popcount sums."""
    return tm_popcount_xla.__wrapped__(
        lit_idx, last, mask_pos, mask_neg, _packed_literals(x_staged, patch)
    )


def _popcount_engine_pallas(
    lit_idx, last, mask_pos, mask_neg, x_staged, *patch,
    weight_planes, block_instructions, block_words, interpret,
):
    """The kernel on the program's resident layout (``kernel_operands``)."""
    sums = tm_popcount_resident.__wrapped__(
        lit_idx, last, mask_pos, mask_neg, _packed_literals(x_staged, patch),
        block_instructions=block_instructions, block_words=block_words,
        interpret=interpret,
    )
    return sum_weight_planes(sums.reshape(weight_planes, -1, sums.shape[1]))


@register_engine("popcount", supports_donation=True, priority=30)
class PopcountEngine(EngineBase):
    """Popcount bitplane engine (kernels/tm_popcount): packed clause
    words end-to-end, class sums via ``lax.population_count`` against the
    program's polarity-bank selection bitplanes.

    The program (operand vectors + class masks) is pushed to the device
    ONCE at ``program()`` (``jax.device_put``); each engine call ships only
    the staging block, donated to XLA so the feature buffer is recycled
    across flushes rather than accumulating.

    A plan with ``position_capacity > 1`` stages images and evaluates
    clauses at patch positions: the program also carries the geometry's
    patch operands (``kernels.tm_popcount.ops.patch_operands``), the step
    forms the patch literals on the device and the kernel ORs clause
    outputs over positions.  A plain TM in such a plan is a 1 x F image
    under a 1 x F window.  With one position the step is the plain one.
    """

    validated_knobs = (
        "instruction_capacity", "feature_capacity", "class_capacity",
        "weight_planes",  # the selection-bank depth is a compiled shape
        "position_capacity", "input_capacity",
    )
    instruction_metric = "includes"  # operand vectors hold includes only
    needs_decoded_plan = True
    evaluates_positions = True

    def __init__(
        self,
        plan: CapacityPlan,
        implementation: str | None = None,
        interpret: bool = False,
    ):
        super().__init__(plan)
        on_tpu = jax.default_backend() == "tpu"
        if implementation is None:
            # the Pallas kernel is the TPU artifact; its interpret-mode
            # emulation loses to the bit-exact XLA twin everywhere else
            implementation = "pallas" if on_tpu else "xla"
        if implementation not in ("pallas", "xla"):
            raise ValueError(
                f"unknown implementation {implementation!r}; "
                f"choose 'pallas' or 'xla'"
            )
        self.implementation = implementation
        if implementation == "pallas":
            if not (on_tpu or interpret):
                raise ValueError(
                    f"implementation='pallas' needs a TPU, and the "
                    f"backend is {jax.default_backend()!r}; pass "
                    f"interpret=True to emulate the kernel, or "
                    f"implementation='xla'"
                )
            bi, bw = kernel_blocks(
                plan.instruction_capacity, plan.batch_words,
                positions=plan.position_capacity,
            )
            self._block_instructions = bi
            engine = functools.partial(
                _popcount_engine_pallas, weight_planes=plan.weight_planes,
                block_instructions=bi, block_words=bw, interpret=interpret,
            )
        else:
            engine = _popcount_engine_xla
        self._fn = _private_jit(
            engine, "tm_popcount_step", donate_argnums=(4,)
        )

    def _program(self, model: CompressedModel, decoded=None) -> Dict[str, Any]:
        p = self.plan
        plan = decoded if decoded is not None else decode_to_plan(model)
        # masks are built at the PLAN's plane depth (not the model's), so
        # the compiled mask shape is a synthesis-time constant: weighted
        # and weightless models swap through the same compiled program
        lit_idx, last, mask_pos, mask_neg = plan_to_popcount_operands(
            plan, p.instruction_capacity, p.class_capacity,
            l2_cap=2 * p.feature_capacity,
            weight_planes=p.weight_planes,
        )
        if self.implementation == "pallas":
            lit_idx, last, mask_pos, mask_neg = kernel_operands(
                lit_idx, last, mask_pos, mask_neg, self._block_instructions
            )
        patch = ()
        if p.position_capacity > 1:
            geom = model.patch or PatchGeometry(
                1, model.n_features, 1, model.n_features)
            patch = tuple(jax.device_put(a) for a in patch_operands(
                geom, p.input_capacity, p.feature_capacity,
                p.position_capacity,
            ))
        # the reprogram is pure data movement: resident on-device until the
        # next swap, never retraced (fixed capacity shapes)
        return {
            "lit_idx": jax.device_put(lit_idx),
            "last": jax.device_put(last),
            "mask_pos": jax.device_put(mask_pos),
            "mask_neg": jax.device_put(mask_neg),
            "patch": patch,
            "n_classes": model.n_classes,
            "n_features": model.n_features,
        }

    def class_sums(self, prog: Dict[str, Any], x: np.ndarray) -> np.ndarray:
        B = x.shape[0]
        with TraceAnnotation(H2D):
            # fresh device copy of the staging block; the engine donates it
            staged = jnp.asarray(self._pad_x(x))
        with TraceAnnotation(LAUNCH):
            sums = self._dispatch(
                prog["lit_idx"], prog["last"],
                prog["mask_pos"], prog["mask_neg"], staged, *prog["patch"],
            )
        with TraceAnnotation(D2H):
            return np.asarray(sums)[: prog["n_classes"], :B].T


@register_engine("sharded", needs_mesh=True, priority=5)
class ShardedEngine(EngineBase):
    """dist.tm_sharded clause-major engine on a (data, model) mesh.

    Built once at CAPACITY shape (classes padded to the model axis, clause
    tables at clause/include capacity); programming a model fills the
    fixed-shape tables, so swaps never touch the compiled shard_map.
    """

    validated_knobs = (
        "feature_capacity", "class_capacity",
        "clause_capacity", "include_capacity", "input_capacity",
    )
    needs_decoded_plan = True

    def __init__(self, plan: CapacityPlan, mesh=None):
        super().__init__(plan)
        if mesh is None:
            mesh = make_mesh((1, 1), ("data", "model"))
        self.mesh = mesh
        cfg = TMShardedConfig(
            name="serve", n_classes=plan.class_capacity,
            n_clauses=plan.clause_capacity,
            n_features=plan.feature_capacity,
            batch=plan.batch_capacity,
            include_cap=plan.include_capacity,
        )
        fn, _ = build_tm_sharded(cfg, mesh)
        # route through _private_jit like every other engine so the
        # compile_cache_size() == 1 contract is enforced uniformly
        self._fn = _private_jit(fn, "tm_sharded_step")
        self._Mp = _pad_to(
            plan.class_capacity, _axis_sizes(mesh).get("model", 1)
        )

    def _program(self, model: CompressedModel, decoded=None) -> Dict[str, Any]:
        p = self.plan
        plan = decoded if decoded is not None else decode_to_plan(model)
        # plan.validate already bounded clauses/includes per class; the
        # table fill re-checks as a corruption guard
        idx, pol = fill_clause_tables(
            plan, self._Mp, p.clause_capacity, p.include_capacity,
            2 * p.feature_capacity,
        )
        return {
            "idx": jnp.asarray(idx), "pol": jnp.asarray(pol),
            "n_classes": model.n_classes,
            "n_features": model.n_features,
        }

    def class_sums(self, prog: Dict[str, Any], x: np.ndarray) -> np.ndarray:
        p = self.plan
        B = x.shape[0]
        lits = np.asarray(
            literals(jnp.asarray(self._pad_features(x), bool))
        ).astype(np.int8)  # [B_cap, 2*F_cap]
        lits1 = np.concatenate(
            [lits, np.ones((p.batch_capacity, 1), np.int8)], axis=1
        )
        sums = self._fn(prog["idx"], prog["pol"], jnp.asarray(lits1))
        return np.asarray(sums)[:B, : prog["n_classes"]]
