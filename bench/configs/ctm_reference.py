"""Plain reference for the class sums of a Convolutional TM (Granmo et
al., arXiv:1905.09688), independent of the program.

An image of ``image_h x image_w`` bits is read through a ``window_h x
window_w`` window at every position, stride 1, row-major (top-left pixel
``(y, x)``).  A patch's features are the window's pixels row-major, then
``y`` and then ``x`` in thermometer code (bit ``t`` is 1 iff the
coordinate is greater than ``t``); literal slot 2k is feature k and slot
2k+1 its negation.  A clause fires on an image when, at some position,
none of its included literals is 0, and it has at least one include;
class ``m``'s sum is the number of its even clauses that fire minus the
number of its odd clauses that fire, and the prediction is the lowest
class with the largest sum.

The count of included literals that are 0 is an integer matrix product
of int8 operands into int32, exact: no float precision is involved.  It
is taken over a chunk of (row, position) pairs at a time, and the clauses
that fire are ORed over the chunks, so that the product of a block of
rows with every position is never held at once.  ``drop_last_include``
drops the last include of every clause: the guarantee that every include
is evaluated, broken, as a truncated instruction stream would break it.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.configs import tm_reference

BLOCK_ROWS = 128  # rows per reference call
PAIRS = 4096  # (row, position) pairs per product: bounds [pairs, M*C]

predictions = tm_reference.predictions


@dataclasses.dataclass(frozen=True)
class Truth:
    """What the reference is given: include actions bool[M, C, 2F] over
    the features of one patch, and the geometry they are read in."""

    actions: np.ndarray
    image: tuple  # (image_h, image_w)
    window: tuple  # (window_h, window_w)

    @property
    def positions(self) -> tuple:
        return (self.image[0] - self.window[0] + 1,
                self.image[1] - self.window[1] + 1)


def drop_last_include(truth: Truth) -> Truth:
    """``truth`` with the highest-slot include of every clause removed."""
    return dataclasses.replace(
        truth, actions=tm_reference.drop_last_include(truth.actions))


def patch_layout(truth: Truth):
    """(pixel int32[P, h*w], coord uint8[P, (H-h)+(W-w)]): the image pixel
    of each window slot, and the thermometer bits of each position."""
    (h_img, w_img), (h, w) = truth.image, truth.window
    py, px = truth.positions
    y, x0 = np.divmod(np.arange(py * px), px)
    dy, dx = np.divmod(np.arange(h * w), w)
    pixel = (y[:, None] + dy) * w_img + x0[:, None] + dx
    coord = np.concatenate([y[:, None] > np.arange(h_img - h),
                            x0[:, None] > np.arange(w_img - w)], axis=1)
    return pixel.astype(np.int32), coord.astype(np.uint8)


@partial(jax.jit, static_argnames="chunk")
def _block_sums(incl, vote, x, pixel, coord, valid, *, chunk: int):
    """int32[B, M] for uint8[B, H*W] images.  ``pixel`` and ``coord`` are
    ``patch_layout``'s, padded to a multiple of ``chunk`` positions; at a
    position where ``valid`` (bool[P]) is False every literal is 0, so no
    clause fires there.  ``incl`` int8[M*C, 2F], ``vote`` int32[M, C]
    (+1/-1, 0 for an empty clause)."""
    b, p = x.shape[0], pixel.shape[0]
    feats = jnp.concatenate(
        [x[:, pixel], jnp.broadcast_to(coord, (b, *coord.shape))], axis=2)
    f = feats.shape[2]  # [B, P, F]
    lits = jnp.stack([feats, 1 - feats], axis=-1).reshape(b, p, 2 * f)
    lits = lits * valid[None, :, None].astype(lits.dtype)
    zeros = (1 - lits).astype(jnp.int8).reshape(b, p // chunk, chunk, 2 * f)

    def fold(fired, z):  # z: int8[B, chunk, 2F]
        missed = jax.lax.dot_general(
            z.reshape(b * chunk, 2 * f), incl, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # included literals that are 0, per (row, position) and clause
        hit = jnp.any((missed == 0).reshape(b, chunk, -1), axis=1)
        return fired | hit, None

    fired, _ = jax.lax.scan(fold, jnp.zeros((b, incl.shape[0]), bool),
                            jnp.moveaxis(zeros, 1, 0))
    fires = fired.astype(jnp.int32).reshape(b, *vote.shape)
    return jnp.sum(fires * vote[None], axis=-1)


def class_sums(truth: Truth, x: np.ndarray,
               block_rows: int = BLOCK_ROWS) -> np.ndarray:
    """int32[B, M] class sums of uint8[B, H*W] images, ``block_rows`` rows
    to a call."""
    acts = np.asarray(truth.actions, bool)
    m, c, l2 = acts.shape
    incl = jnp.asarray(acts.reshape(m * c, l2), jnp.int8)
    pol = np.where(np.arange(c) % 2 == 0, 1, -1)
    vote = jnp.asarray(pol[None, :] * acts.any(axis=-1), jnp.int32)
    pixel, coord = patch_layout(truth)
    n_pos = pixel.shape[0]
    chunk = max(1, min(n_pos, PAIRS // block_rows))
    pad_p = -n_pos % chunk
    layout = (jnp.asarray(np.pad(pixel, ((0, pad_p), (0, 0)))),
              jnp.asarray(np.pad(coord, ((0, pad_p), (0, 0)))),
              jnp.asarray(np.arange(n_pos + pad_p) < n_pos))
    x = np.asarray(x, np.uint8)
    out = np.empty((x.shape[0], m), np.int32)
    for lo in range(0, x.shape[0], block_rows):
        block = x[lo:lo + block_rows]
        pad = block_rows - block.shape[0]
        full = np.pad(block, ((0, pad), (0, 0))) if pad else block
        sums = np.asarray(_block_sums(incl, vote, jnp.asarray(full), *layout,
                                      chunk=chunk))
        out[lo:lo + block.shape[0]] = sums[:block.shape[0]]
    return out
