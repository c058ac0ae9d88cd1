"""The formal Engine plugin protocol + registry.

An *engine* is one realization of the runtime-tunable accelerator: a
fixed-capacity compiled artifact that models are programmed INTO (pure
data movement) rather than compiled FOR.  Every engine honours one
contract:

  ``program(model)``        host-side reprogram: decode the compressed
                            model into the engine's fixed-capacity
                            buffers.  Capacity validation is uniform —
                            the base class runs ``plan.validate(model)``
                            (raising ``CapacityExceeded``) before the
                            engine-specific ``_program``.
  ``class_sums(prog, x)``   {0,1}[B, F] -> int32[B, n_classes]
  ``compile_cache_size()``  # compiled variants of THIS engine's jitted
                            program — the zero-resynthesis property; must
                            stay 1 across model swaps.
  ``staging``               the engine's preallocated
                            [batch_capacity, input_capacity] uint8
                            staging array; the batcher packs request
                            rows straight into it
                            (``Batcher.next_batch(out=...)``).

Engines self-describe through capability flags set by the
``@register_engine`` decorator:

  ``supports_donation``     the engine donates its per-call device
                            feature buffer to XLA (the facade scopes the
                            off-TPU "donation declined" warning to these
                            call sites only);
  ``needs_mesh``            the engine consumes a device mesh (today:
                            the sharded clause-major shard_map);
  ``priority``              relative speed rank used by ``select_engine``
                            to auto-pick the fastest eligible engine;
  ``validated_knobs``       which ``CapacityPlan`` buffers the engine's
                            layout actually instantiates — ``program``
                            validates exactly those (e.g. the clause
                            tables bound only the sharded engine);
  ``evaluates_positions``   the engine evaluates clauses at patch
                            positions (a convolutional TM, the
                            ``position_capacity`` knob); the others
                            refuse a model with more than one position.

Construction is uniform: ``make_engine(name, plan, **options)`` — mesh
and implementation knobs are per-engine options, not special-cased
branches.
"""

from __future__ import annotations

import contextlib
import warnings
from typing import Any, Dict, Optional, Protocol, runtime_checkable

import jax
import numpy as np

from ..core.compress import decode_to_plan
from .capacity import CapacityExceeded, CapacityPlan

# name -> engine class; populated by @register_engine (engines.py registers
# the four built-ins on import)
ENGINES: Dict[str, type] = {}


@runtime_checkable
class Engine(Protocol):
    """Structural type of an accelerator engine (see module docstring)."""

    name: str
    supports_donation: bool
    needs_mesh: bool
    priority: int
    validated_knobs: tuple
    plan: CapacityPlan

    def program(self, model) -> Dict[str, Any]: ...

    def class_sums(self, prog: Dict[str, Any], x: np.ndarray) -> np.ndarray: ...

    def compile_cache_size(self) -> int: ...


def register_engine(
    name: str,
    *,
    supports_donation: bool = False,
    needs_mesh: bool = False,
    priority: int = 0,
):
    """Class decorator registering an engine plugin under ``name`` and
    stamping its capability flags.  Re-registering a taken name raises —
    plugin identity must be unambiguous for auto-selection to be
    deterministic."""

    def deco(cls):
        if name in ENGINES and ENGINES[name] is not cls:
            raise ValueError(
                f"engine name {name!r} already registered to "
                f"{ENGINES[name].__name__}"
            )
        cls.name = name
        cls.supports_donation = bool(supports_donation)
        cls.needs_mesh = bool(needs_mesh)
        cls.priority = int(priority)
        ENGINES[name] = cls
        return cls

    return deco


def engine_names() -> list:
    return sorted(ENGINES)


def select_engine(
    plan: Optional[CapacityPlan] = None, *, mesh=None
) -> str:
    """Deterministically pick the fastest eligible engine name.

    With a mesh, mesh-consuming engines (``needs_mesh``) are the eligible
    set — the caller provisioned devices for exactly them.  Without one,
    the fastest mesh-free engine wins.  Ties break lexicographically so
    selection is stable across processes.  ``plan`` is part of the
    contract (today every engine serves every plan point; a plugin whose
    eligibility depends on the capacity point will consume it here)."""
    if mesh is not None:
        eligible = [c for c in ENGINES.values() if c.needs_mesh]
    else:
        eligible = [c for c in ENGINES.values() if not c.needs_mesh]
    if not eligible:
        raise ValueError(
            f"no eligible engine (mesh={'yes' if mesh is not None else 'no'}; "
            f"registered: {engine_names() or 'none'})"
        )
    return max(eligible, key=lambda c: (c.priority, c.name)).name


def make_engine(
    engine: "str | EngineBase", plan: CapacityPlan, *, mesh=None, **options
) -> "EngineBase":
    """Uniform plugin construction: name (or a built instance) -> engine.

    ``options`` go to the engine verbatim; the mesh is forwarded only to
    engines that declare ``needs_mesh`` (capability-flag-driven, not a
    per-name special case)."""
    if isinstance(engine, EngineBase):
        return engine
    if engine not in ENGINES:
        raise ValueError(
            f"unknown engine {engine!r}; registered: {engine_names()}"
        )
    cls = ENGINES[engine]
    if cls.needs_mesh and mesh is not None:
        options = {**options, "mesh": mesh}
    return cls(plan, **options)


def _private_jit(fn, name: str, **jit_kwargs):
    """jit over a FRESH closure: JAX keys its compilation cache on the
    callable, so wrapping gives this engine instance its own cache.

    ``name`` names the closure, and so the compiled program
    (``jit_<name>``) and its device ops in a profiler trace."""

    def inner(*args, **kwargs):
        return fn(*args, **kwargs)

    inner.__name__ = inner.__qualname__ = name
    return jax.jit(inner, **jit_kwargs)


@contextlib.contextmanager
def _donation_declined_ok():
    """Buffer donation is an optimization hint; off-TPU XLA may decline it
    and warn — expected on CPU test/CI containers, not actionable.  Scoped
    to the donating engine's dispatch instead of mutating process-global
    warning state at import (the old module-level ``filterwarnings``)."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


class EngineBase:
    """Shared engine mechanics: capacity validation, the staging array,
    private-jit dispatch (with donation-warning scoping for donating
    engines)."""

    name = "?"
    supports_donation = False
    needs_mesh = False
    priority = 0
    # which plan buffers this engine's layout instantiates (subclasses
    # narrow this; the clause tables, e.g., only exist in the sharded
    # layout).  CapacityPlan.for_models always provisions the full set.
    validated_knobs: tuple = CapacityPlan.KNOBS
    # what instruction_capacity must hold for THIS layout: "stream" = the
    # full uint16 stream (the interp engine's instruction memory);
    # "includes" = only the include slots (the plan/popcount operand
    # vectors — boundary EXTENDs never materialize there, so an
    # EXTEND-heavy stream still fits)
    instruction_metric = "stream"
    # engines whose reprogram consumes the DecodedPlan set this; the base
    # decodes the stream exactly once and shares it between validation
    # and _program (a swap must not pay repeated host-side stream walks)
    needs_decoded_plan = False
    # whether the layout ORs clause outputs over patch positions; an engine
    # that does not would serve a convolutional TM as a plain one
    evaluates_positions = False

    def __init__(self, plan: CapacityPlan):
        self.plan = plan
        self._staging: Optional[np.ndarray] = None

    # legacy spelling (ServeCapacity era); same object
    @property
    def capacity(self) -> CapacityPlan:
        return self.plan

    def model_violations(self, model, decoded=None) -> list:
        """``(knob, required, provided)`` for every buffer of THIS layout
        the model blows through, honouring the engine's
        ``instruction_metric`` (a plan/popcount deployment only needs the
        include slots, not the full stream depth)."""
        knobs = list(self.validated_knobs)
        metric_is_includes = (
            "instruction_capacity" in knobs
            and self.instruction_metric == "includes"
        )
        if metric_is_includes:
            knobs.remove("instruction_capacity")
        if decoded is None and (
            metric_is_includes
            or set(knobs) & {"clause_capacity", "include_capacity"}
        ):
            # both the clause-extent requirements and the include metric
            # read the decoded plan: walk the stream once, share it
            decoded = decode_to_plan(model)
        bad = self.plan.violations(model, knobs, decoded)
        if metric_is_includes and (
            decoded.n_includes > self.plan.instruction_capacity
        ):
            bad.insert(0, (
                "instruction_capacity", decoded.n_includes,
                self.plan.instruction_capacity,
            ))
        return bad

    def validate_model(self, model, decoded=None) -> None:
        """Raise ``CapacityExceeded`` when ``model`` doesn't fit this
        engine's buffers (what ``Accelerator.compile`` gates on — the
        exact check the load path will repeat).  An engine that evaluates
        no positions refuses a convolutional TM (``ValueError``)."""
        if model.n_positions > 1 and not self.evaluates_positions:
            able = sorted(n for n, c in ENGINES.items()
                          if c.evaluates_positions)
            raise ValueError(
                f"engine {self.name!r} evaluates each clause at one "
                f"position, and the model is a convolutional TM with "
                f"{model.n_positions} patch positions; serve it on an "
                f"engine that ORs clauses over positions: {able}"
            )
        bad = self.model_violations(model, decoded)
        if bad:
            raise CapacityExceeded(*bad[0])

    def program(self, model) -> Dict[str, Any]:
        """Validate ``model`` against the buffers this engine actually
        has, then run the engine-specific reprogram (pure data
        movement).  The instruction stream is decoded at most ONCE per
        install, shared between validation and the reprogram."""
        decoded = decode_to_plan(model) if self.needs_decoded_plan else None
        self.validate_model(model, decoded)
        return self._program(model, decoded)

    def _program(self, model, decoded) -> Dict[str, Any]:
        raise NotImplementedError

    def class_sums(self, prog: Dict[str, Any], x: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def compile_cache_size(self) -> int:
        return self._fn._cache_size()

    def _dispatch(self, *args):
        """Run the engine's private jit; donating engines scope the
        off-TPU donation-declined warning to exactly this call site."""
        if self.supports_donation:
            with _donation_declined_ok():
                return self._fn(*args)
        return self._fn(*args)

    @property
    def staging(self) -> np.ndarray:
        """The engine's preallocated [batch_capacity, input_capacity]
        uint8 staging array (a plain TM's features, a convolutional TM's
        images).  The batcher packs request rows straight into it
        (``Batcher.next_batch(out=...)``) and the engines consume it as
        their one fixed operand shape — no per-flush host allocation."""
        if self._staging is None:
            p = self.plan
            self._staging = np.zeros(
                (p.batch_capacity, p.input_capacity), np.uint8
            )
        return self._staging

    def _pad_features(self, x: np.ndarray) -> np.ndarray:
        """``_pad_x`` cut to the ``feature_capacity`` columns a plain TM's
        literals come from (the whole staging array unless the plan
        stages wider inputs for a convolutional TM)."""
        return self._pad_x(x)[:, : self.plan.feature_capacity]

    def _pad_x(self, x: np.ndarray) -> np.ndarray:
        """{0,1}[B, X] -> the staging array (zero-padded to capacity).

        When ``x`` is already a view of ``self.staging`` (the batcher
        packed it there), it is returned as-is — zero copies."""
        p = self.plan
        B, F = x.shape
        if B > p.batch_capacity:
            raise CapacityExceeded(
                "batch_words", -(-B // 32), p.batch_words, "batch"
            )
        if F > p.input_capacity:
            raise CapacityExceeded(
                "input_capacity", F, p.input_capacity, "input width"
            )
        st = self.staging
        if np.shares_memory(x, st):
            if (x.__array_interface__["data"][0]
                    == st.__array_interface__["data"][0]):
                # a leading view — the batcher packed rows [0, B) in place
                # and zeroed the remainder (next_batch(out=) contract)
                return st
            # any other overlapping view would be corrupted by the zero
            # fill below; detach it first
            x = np.array(x)
        st.fill(0)
        st[:B, :F] = x
        return st
