"""Plain reference for TM class sums, independent of the program.

A clause fires on a row when none of its included literals is 0 and it
has at least one include; class ``m``'s sum is the number of its even
clauses that fire minus the number of its odd clauses that fire, and the
prediction is the lowest class with the largest sum.  Literal slot 2k is
feature k and slot 2k+1 its negation.

The count of included literals that are 0 is one integer matrix product,
exact in int32.  ``control`` drops the last include of every clause: the
configuration's guarantee that every include is evaluated, broken, as a
truncated instruction stream would break it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 8192  # rows per reference call: bounds the [rows, M*C] counts


def drop_last_include(actions: np.ndarray) -> np.ndarray:
    """``actions`` with the highest-slot include of every clause removed."""
    out = np.array(actions, bool)
    l2 = out.shape[-1]
    last = l2 - 1 - np.argmax(out[..., ::-1], axis=-1)
    has = out.any(axis=-1)
    m_idx, c_idx = np.nonzero(has)
    out[m_idx, c_idx, last[m_idx, c_idx]] = False
    return out


@jax.jit
def _block_sums(incl: jax.Array, vote: jax.Array, x: jax.Array) -> jax.Array:
    """int32[B, M] for uint8[B, F] rows; ``incl`` int8[M*C, 2F], ``vote``
    int32[M, C] (+1/-1, 0 for an empty clause)."""
    lits = jnp.stack([x, 1 - x], axis=-1).reshape(x.shape[0], -1)
    zeros = (1 - lits).astype(jnp.int8)
    missed = jax.lax.dot_general(
        zeros, incl, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [B, M*C]: included literals that are 0
    fires = (missed == 0).astype(jnp.int32).reshape(x.shape[0], *vote.shape)
    return jnp.sum(fires * vote[None], axis=-1)


def class_sums(actions: np.ndarray, x: np.ndarray,
               block_rows: int = BLOCK_ROWS) -> np.ndarray:
    """int32[B, M] class sums of uint8[B, F] rows under bool[M, C, 2F],
    ``block_rows`` rows to a call."""
    m, c, l2 = actions.shape
    incl = jnp.asarray(actions.reshape(m * c, l2), jnp.int8)
    pol = np.where(np.arange(c) % 2 == 0, 1, -1)
    vote = jnp.asarray(pol[None, :] * actions.any(axis=-1), jnp.int32)
    x = np.asarray(x, np.uint8)
    out = np.empty((x.shape[0], m), np.int32)
    for lo in range(0, x.shape[0], block_rows):
        block = x[lo:lo + block_rows]
        pad = block_rows - block.shape[0]
        full = np.pad(block, ((0, pad), (0, 0))) if pad else block
        sums = np.asarray(_block_sums(incl, vote, jnp.asarray(full)))
        out[lo:lo + block.shape[0]] = sums[:block.shape[0]]
    return out


def predictions(sums: np.ndarray) -> np.ndarray:
    """int32[B]: the lowest class index among the largest sums."""
    return np.argmax(sums, axis=1).astype(np.int32)
