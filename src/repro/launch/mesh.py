"""Production mesh construction.

NOTE: this module never touches jax device state at import time; meshes are
built inside functions so the dry-run's XLA_FLAGS (512 host devices) or the
test environment (1 device) decide what exists.
"""

from __future__ import annotations

from ..dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: pod = cross-pod data parallelism (DCN), data = in-pod DP/FSDP,
    model = TP/EP.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CI-scale sharding tests (requires host device count)."""
    return make_mesh((n_data, n_model), ("data", "model"))
