"""The formal TrainEngine plugin protocol + registry.

The training twin of ``accel.engine``: where an inference *engine* is one
realization of the runtime-tunable accelerator, a *train engine* is one
realization of the Fig-8 training node.  Every plugin honours one
contract, built around the fold-in seeding contract of ``core.train``:

  ``prepare(state)``        canonical ``int32[M, C, 2F]`` TA state ->
                            the engine's internal representation (the
                            packed engine keeps int8 across steps; the
                            reference/sharded engines are identity)
  ``canonical(internal)``   internal -> canonical int32 state (what
                            checkpoints, compressors and other engines
                            consume — the (key, step, state) triple
                            round-trips across backends)
  ``fit_step(internal, key, xb, yb, step=)``
                            one resumable update: the batch trains under
                            ``fold_in(key, step)``, sample ``i`` under
                            ``fold_in(call_key, i)``.  Every registered
                            engine produces the BIT-IDENTICAL canonical
                            state for the same (key, step, batch) —
                            backend choice is a speed knob, never a
                            semantics knob (property-tested).

Engines self-describe through capability flags set by
``@register_train_engine``:

  ``needs_mesh``            consumes a device mesh (the class-sharded
                            dist step);
  ``priority``              relative speed rank used by
                            ``select_train_engine`` to auto-pick the
                            fastest eligible engine;

plus a per-class ``supports(cfg)`` hook for representation limits (the
packed int8 layout holds at most 128 states per action).

No train engine trains a Convolutional TM (``TMConfig.patch`` with more
than one position: its feedback samples a position per clause, which
none of them implements).  Each refuses such a config at construction,
and ``select_train_engine`` finds none eligible, so the Fig-8 loop never
trains one as a plain TM.

Construction is uniform: ``make_train_engine(name, cfg, *, mesh=None,
plan=None, **options)`` — mesh and implementation knobs are per-engine
options, not special-cased branches (``RecalWorker`` no longer branches
on ``use_dist_mesh``-style arguments).  ``plan`` opts every engine into
the negotiated ``CapacityPlan`` batch envelope: a training batch wider
than ``plan.batch_words * 32`` raises the structured
``CapacityExceeded``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from ..core.tm import TMConfig
from ..core.train import fit_step as _core_fit_step
from ..core.train import validate_batch_capacity
from ..kernels.tm_train import (
    fused_train_batch,
    pack_ta_state,
    supports_packed_states,
    unpack_ta_state,
)

Array = jax.Array

# name -> engine class; populated by @register_train_engine (the three
# built-ins below register on import)
TRAIN_ENGINES: Dict[str, type] = {}


@runtime_checkable
class TrainEngine(Protocol):
    """Structural type of a training backend (see module docstring)."""

    name: str
    needs_mesh: bool
    priority: int
    cfg: TMConfig

    def prepare(self, state) -> Any: ...

    def canonical(self, internal) -> Array: ...

    def fit_step(self, internal, key, xb, yb, *, step: int) -> Any: ...


def register_train_engine(
    name: str, *, needs_mesh: bool = False, priority: int = 0
):
    """Class decorator registering a train-engine plugin under ``name``
    and stamping its capability flags.  Re-registering a taken name
    raises — auto-selection must be deterministic."""

    def deco(cls):
        if name in TRAIN_ENGINES and TRAIN_ENGINES[name] is not cls:
            raise ValueError(
                f"train engine name {name!r} already registered to "
                f"{TRAIN_ENGINES[name].__name__}"
            )
        cls.name = name
        cls.needs_mesh = bool(needs_mesh)
        cls.priority = int(priority)
        TRAIN_ENGINES[name] = cls
        return cls

    return deco


def train_engine_names() -> list:
    return sorted(TRAIN_ENGINES)


def select_train_engine(
    cfg: Optional[TMConfig] = None, *, mesh=None
) -> str:
    """Deterministically pick the fastest eligible train engine name.

    With a mesh, mesh-consuming engines are the eligible set — the
    caller provisioned devices for exactly them.  Without one, the
    fastest mesh-free engine that ``supports(cfg)`` wins (the packed
    engine bows out for configs outside its int8 state range).  Ties
    break lexicographically so selection is stable across processes."""
    if cfg is not None:
        refuse_convolutional(cfg)
    eligible = [
        c
        for c in TRAIN_ENGINES.values()
        if c.needs_mesh == (mesh is not None)
        and (cfg is None or c.supports(cfg))
    ]
    if not eligible:
        raise ValueError(
            f"no eligible train engine "
            f"(mesh={'yes' if mesh is not None else 'no'}; "
            f"registered: {train_engine_names() or 'none'})"
        )
    return max(eligible, key=lambda c: (c.priority, c.name)).name


def make_train_engine(
    engine: "str | TrainEngineBase",
    cfg: TMConfig,
    *,
    mesh=None,
    plan=None,
    **options,
) -> "TrainEngineBase":
    """Uniform plugin construction: name (or a built instance) -> engine.

    ``options`` go to the engine verbatim; the mesh is forwarded only to
    engines that declare ``needs_mesh`` (capability-flag-driven, the same
    rule as ``accel.make_engine``)."""
    if isinstance(engine, TrainEngineBase):
        return engine
    if engine not in TRAIN_ENGINES:
        raise ValueError(
            f"unknown train engine {engine!r}; registered: "
            f"{train_engine_names()}"
        )
    cls = TRAIN_ENGINES[engine]
    if cls.needs_mesh and mesh is not None:
        options = {**options, "mesh": mesh}
    return cls(cfg, plan=plan, **options)


def refuse_convolutional(cfg: TMConfig) -> None:
    """Raise ``ValueError`` for a Convolutional TM with more than one
    position: no train engine implements its position-sampling feedback,
    and training it as a plain TM would publish a wrong model."""
    if cfg.n_positions > 1:
        raise ValueError(
            f"no train engine trains a convolutional TM ({cfg.n_positions} "
            f"patch positions): recalibration serves it but cannot "
            f"retrain it"
        )


class TrainEngineBase:
    """Shared train-engine mechanics: batch-envelope validation and the
    canonical-representation identity hooks."""

    name = "?"
    needs_mesh = False
    priority = 0

    def __init__(self, cfg: TMConfig, *, plan=None):
        refuse_convolutional(cfg)
        self.cfg = cfg
        self.plan = plan

    @classmethod
    def supports(cls, cfg: TMConfig) -> bool:
        """Whether this engine's representation can hold ``cfg`` (the
        packed int8 layout narrows this; the default is unconditional)."""
        return True

    # -- representation ------------------------------------------------------

    def prepare(self, state) -> Any:
        """Canonical int32 state -> engine-internal representation.

        Always a fresh buffer: train steps DONATE the internal state, so
        aliasing the caller's array would delete it out from under them."""
        return jnp.array(state)

    def canonical(self, internal) -> Array:
        """Engine-internal representation -> canonical int32 state."""
        return internal

    # -- the step ------------------------------------------------------------

    def fit_step(self, internal, key, xb, yb, *, step: int) -> Any:
        """One resumable update under the fold-in seeding contract.
        Validates the negotiated batch envelope (when a plan was given)
        before dispatching to the engine-specific ``_fit_step``."""
        validate_batch_capacity(xb.shape[0], self.plan)
        return self._fit_step(internal, key, xb, yb, step=step)

    def _fit_step(self, internal, key, xb, yb, *, step: int) -> Any:
        raise NotImplementedError


@register_train_engine("reference", priority=1)
class ReferenceTrainEngine(TrainEngineBase):
    """The host reference path: ``core.train.fit_step`` on the canonical
    int32 state.  ``parallel=True`` (summed-delta) is the default — the
    semantics every other engine is bit-identical to; ``parallel=False``
    opts into the sequential online scan (a different, slower contract
    no other engine implements)."""

    def __init__(self, cfg: TMConfig, *, plan=None, parallel: bool = True):
        super().__init__(cfg, plan=plan)
        self.parallel = bool(parallel)

    def _fit_step(self, internal, key, xb, yb, *, step: int):
        return _core_fit_step(
            self.cfg, internal, key, xb, yb,
            step=step, parallel=self.parallel,
        )


@register_train_engine("packed", priority=2)
class PackedTrainEngine(TrainEngineBase):
    """The fused packed-TA path (``kernels.tm_train``): int8 states in
    the flat (clauses, literals, 2) layout, clause-eval + feedback + TA
    update in one compiled pass over packed uint32 literal bitplanes.
    Bit-identical to ``reference`` and internal-state persistent: the
    int8 tensor survives across steps; conversion happens only at the
    ``prepare``/``canonical`` checkpoint boundary."""

    def __init__(self, cfg: TMConfig, *, plan=None):
        super().__init__(cfg, plan=plan)
        if not supports_packed_states(cfg):
            raise ValueError(
                f"n_states={cfg.n_states} exceeds the packed int8 TA "
                f"range (<= 128); use the 'reference' or 'sharded' train "
                f"engines for this config"
            )

    @classmethod
    def supports(cls, cfg: TMConfig) -> bool:
        return supports_packed_states(cfg)

    def prepare(self, state) -> Array:
        return pack_ta_state(self.cfg, state)

    def canonical(self, internal) -> Array:
        return unpack_ta_state(self.cfg, internal)

    def _fit_step(self, internal, key, xb, yb, *, step: int):
        kb = jax.random.fold_in(key, step)
        return fused_train_batch(self.cfg, internal, kb, xb, yb)


@register_train_engine("sharded", needs_mesh=True, priority=1)
class ShardedTrainEngine(TrainEngineBase):
    """The dist-mesh class-sharded step (``dist.steps.make_tm_train_step``:
    classes over ``model``, batch over the data axes, psum'd integer
    deltas — bit-identical to the reference on any mesh).

    The sharded step compiles for ONE batch size.  ``batch`` pins it at
    construction; otherwise it binds to the first batch seen.  Other
    batch sizes fall back to the reference path (bit-identical anyway) —
    ragged tail batches never force a recompile, the same discipline the
    serving engines keep (``compile_cache_size() == 1``)."""

    def __init__(self, cfg: TMConfig, *, mesh, plan=None, batch: int = 0):
        super().__init__(cfg, plan=plan)
        self.mesh = mesh
        self._step = None
        self._batch = int(batch)
        if self._batch:
            self._build(self._batch)

    def _build(self, batch: int) -> None:
        from ..dist.steps import make_tm_train_step

        self._step = make_tm_train_step(self.cfg, self.mesh, batch=batch)
        self._batch = batch

    def _fit_step(self, internal, key, xb, yb, *, step: int):
        if self._step is None:
            self._build(int(xb.shape[0]))
        if xb.shape[0] == self._batch:
            # same bits as the local path: fold_in(key, step) is the call
            # key, global sample i trains under fold_in(call_key, i)
            kb = jax.random.fold_in(key, step)
            return self._step(internal, kb, xb, yb)
        return _core_fit_step(
            self.cfg, internal, key, xb, yb, step=step, parallel=True
        )
