"""Dense (vanilla) Tsetlin Machine model in JAX.

The TM model for M classes, C clauses/class, F Boolean features:
  * TA state tensor  S  : int32[M, C, 2F]   in [1, 2N]   (N = ``n_states``)
  * include action   A  : bool [M, C, 2F]   A = S > N
  * literal order is **interleaved**: slot k corresponds to feature k>>1,
    complemented iff k&1 == 1.  This keeps within-clause include offsets
    strictly positive for the compressed encoding (see compress.py).

Clause semantics:
  train:     empty clause (no includes) outputs 1
  inference: empty clause outputs 0

Convolutional TM (``TMConfig.patch``, Granmo et al., "The Convolutional
Tsetlin Machine", arXiv:1905.09688): a datapoint is an image, read through
a window at every position (``PatchGeometry``).  The F features a clause
sees are those of one patch; a clause fires on the image iff it fires at
some position (OR over positions), and votes as in the plain TM.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PatchGeometry:
    """A ``window_h x window_w`` window slid with stride 1 over an
    ``image_h x image_w`` Boolean image, row-major: position
    ``p = y * positions_x + x`` has its top-left pixel at ``(y, x)``.

    The features of the patch at ``(y, x)``, in this order:

      * the window's pixels, row-major: feature ``dy * window_w + dx`` is
        pixel ``(y + dy, x + dx)``;
      * ``y`` in thermometer code, ``image_h - window_h`` bits: bit ``t``
        is 1 iff ``y > t``;
      * ``x`` likewise, ``image_w - window_w`` bits.

    A window as large as the image has one position and no coordinate
    bits, so its features are the image's pixels: the plain TM."""

    image_h: int
    image_w: int
    window_h: int
    window_w: int

    def __post_init__(self):
        for side in ("h", "w"):
            image, window = (getattr(self, f"image_{side}"),
                             getattr(self, f"window_{side}"))
            if not 1 <= window <= image:
                raise ValueError(
                    f"PatchGeometry needs 1 <= window_{side} <= "
                    f"image_{side}, got window {window} over image {image}"
                )

    @property
    def positions_y(self) -> int:
        return self.image_h - self.window_h + 1

    @property
    def positions_x(self) -> int:
        return self.image_w - self.window_w + 1

    @property
    def n_positions(self) -> int:
        return self.positions_y * self.positions_x

    @property
    def n_features(self) -> int:
        """Features of one patch: pixels, then the two thermometer codes."""
        return (self.window_h * self.window_w
                + (self.image_h - self.window_h)
                + (self.image_w - self.window_w))

    @property
    def input_width(self) -> int:
        """Pixels of the image: the bits a request row carries."""
        return self.image_h * self.image_w

    def feature_sources(self) -> np.ndarray:
        """int32[F, P]: where feature ``k`` of the patch at position ``p``
        comes from — pixel ``q`` of the image for ``q < input_width``, the
        constant 0 for ``input_width`` and the constant 1 for
        ``input_width + 1`` (the thermometer bits)."""
        ys, xs = np.divmod(np.arange(self.n_positions), self.positions_x)
        dy, dx = np.divmod(np.arange(self.window_h * self.window_w),
                           self.window_w)
        pixels = (ys + dy[:, None]) * self.image_w + xs + dx[:, None]
        zero, one = self.input_width, self.input_width + 1
        ny, nx = self.image_h - self.window_h, self.image_w - self.window_w
        therm_y = np.where(ys > np.arange(ny)[:, None], one, zero)
        therm_x = np.where(xs > np.arange(nx)[:, None], one, zero)
        return np.concatenate([pixels, therm_y, therm_x]).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class TMConfig:
    n_classes: int
    n_clauses: int          # clauses per class; polarity alternates +,-,+,-,...
    n_features: int         # Boolean features (literals = 2 * n_features)
    n_states: int = 128     # per-action state count N; S in [1, 2N]
    threshold: int = 15     # T
    specificity: float = 3.9  # s
    boost_true_positive: bool = True
    # Convolutional TM: clauses see the patches of an image (n_features is
    # then the patch's feature count); None is the plain TM
    patch: Optional[PatchGeometry] = None

    def __post_init__(self):
        if self.patch is not None and self.n_features != self.patch.n_features:
            raise ValueError(
                f"a convolutional TM's n_features is its patch's feature "
                f"count {self.patch.n_features}, got {self.n_features}"
            )

    @property
    def n_literals(self) -> int:
        return 2 * self.n_features

    @property
    def n_positions(self) -> int:
        """Positions a clause is evaluated at (1 for the plain TM)."""
        return 1 if self.patch is None else self.patch.n_positions

    @property
    def n_tas(self) -> int:
        return self.n_classes * self.n_clauses * self.n_literals


def init_state(cfg: TMConfig, key: Array) -> Array:
    """TA states start on the Exclude side of the decision boundary (= N)."""
    del key  # deterministic init; kept for interface symmetry
    return jnp.full(
        (cfg.n_classes, cfg.n_clauses, cfg.n_literals), cfg.n_states, dtype=jnp.int32
    )


def include_actions(cfg: TMConfig, state: Array) -> Array:
    """bool[M, C, 2F] — True where the TA action is Include."""
    return state > cfg.n_states


def state_from_actions(cfg: TMConfig, actions) -> Array:
    """Minimal TA state tensor realizing the given include mask — the
    inverse of ``include_actions`` (tests/benches build models straight
    from action masks with it)."""
    a = jnp.asarray(actions, dtype=jnp.bool_)
    return jnp.where(a, cfg.n_states + 1, cfg.n_states).astype(jnp.int32)


def literals(x: Array) -> Array:
    """Boolean features -> interleaved literals.

    x: bool/int {0,1}[..., F]  ->  {0,1}[..., 2F] with slot 2k = x_k,
    slot 2k+1 = NOT x_k.
    """
    x = x.astype(jnp.bool_)
    inter = jnp.stack([x, ~x], axis=-1)  # [..., F, 2]
    return inter.reshape(*x.shape[:-1], x.shape[-1] * 2)


def clause_outputs(
    cfg: TMConfig, actions: Array, lits: Array, *, training: bool
) -> Array:
    """Clause outputs for one datapoint.

    actions: bool[M, C, 2F]; lits: bool[2F]  ->  bool[M, C]
    """
    # A clause fires iff every included literal is 1.
    sat = jnp.all(jnp.where(actions, lits, True), axis=-1)  # [M, C]
    nonempty = jnp.any(actions, axis=-1)  # [M, C]
    if training:
        return sat
    return sat & nonempty


def clause_polarities(cfg: TMConfig) -> Array:
    """int32[C]: +1 for even clause index, -1 for odd."""
    idx = jnp.arange(cfg.n_clauses)
    return jnp.where(idx % 2 == 0, 1, -1).astype(jnp.int32)


def class_sums(cfg: TMConfig, actions: Array, lits: Array, *, training: bool) -> Array:
    """int32[M] class sums for one datapoint."""
    c = clause_outputs(cfg, actions, lits, training=training).astype(jnp.int32)
    pol = clause_polarities(cfg)
    return jnp.sum(c * pol[None, :], axis=-1)


def patches(geom: PatchGeometry, x: Array) -> Array:
    """Images -> patch features: {0,1}[B, H*W] -> bool[B, P, F], in the
    feature order of ``PatchGeometry`` (window slices, then thermometer
    codes of the position)."""
    b = x.shape[0]
    py, px = geom.positions_y, geom.positions_x
    img = x.astype(jnp.bool_).reshape(b, geom.image_h, geom.image_w)
    pixels = jnp.stack(
        [img[:, dy:dy + py, dx:dx + px]
         for dy in range(geom.window_h) for dx in range(geom.window_w)],
        axis=-1,
    )  # [B, Py, Px, h*w]
    therm_y = jnp.arange(py)[:, None] > jnp.arange(geom.image_h - geom.window_h)
    therm_x = jnp.arange(px)[:, None] > jnp.arange(geom.image_w - geom.window_w)
    coords = jnp.concatenate([
        jnp.broadcast_to(therm_y[:, None, :], (py, px, therm_y.shape[1])),
        jnp.broadcast_to(therm_x[None, :, :], (py, px, therm_x.shape[1])),
    ], axis=-1)
    feats = jnp.concatenate(
        [pixels, jnp.broadcast_to(coords, (b, *coords.shape))], axis=-1)
    return feats.reshape(b, py * px, geom.n_features)


def conv_clause_outputs(actions: Array, patch_lits: Array) -> Array:
    """Inference clause outputs of a convolutional TM for one image.

    actions: bool[M, C, 2F]; patch_lits: bool[P, 2F] -> bool[M, C]: a
    clause fires iff all its included literals are 1 at some position,
    and it has an include."""
    sat = jax.vmap(
        lambda lits: jnp.all(jnp.where(actions, lits, True), axis=-1)
    )(patch_lits)  # [P, M, C]
    return jnp.any(sat, axis=0) & jnp.any(actions, axis=-1)


def _inference_sums(cfg: TMConfig, actions: Array, x: Array,
                    vote: Array) -> Array:
    """int32[B, M]: inference clause outputs times ``vote`` (int32[1, C]
    or [M, C]), summed per class, for input rows x {0,1}[B, input_width]."""
    if cfg.patch is None:
        def fires(row):
            return clause_outputs(cfg, actions, row, training=False)

        lits = literals(x)
    else:
        fires = partial(conv_clause_outputs, actions)
        lits = literals(patches(cfg.patch, x))  # [B, P, 2F]
    return jax.vmap(
        lambda row: jnp.sum(fires(row).astype(jnp.int32) * vote, axis=-1)
    )(lits)


@partial(jax.jit, static_argnums=0)
def predict(cfg: TMConfig, state: Array, x: Array) -> Array:
    """Batched dense prediction. x: {0,1}[B, input_width] -> int32[B]."""
    return jnp.argmax(batch_class_sums(cfg, state, x), axis=-1).astype(
        jnp.int32)


@partial(jax.jit, static_argnums=0)
def batch_class_sums(cfg: TMConfig, state: Array, x: Array) -> Array:
    """int32[B, M] inference-semantics class sums (oracle for all fast
    paths); x is {0,1}[B, input_width], images for a convolutional TM."""
    actions = include_actions(cfg, state)
    return _inference_sums(cfg, actions, x, clause_polarities(cfg)[None, :])


@partial(jax.jit, static_argnums=0)
def batch_class_sums_weighted(
    cfg: TMConfig, state: Array, x: Array, weights: "Array | None" = None
) -> Array:
    """int32[B, M] class sums with per-clause vote weights (repro.prune).

    ``weights`` is int[M, C]; each clause votes ``weight * pol`` instead of
    ``pol``.  ``None`` (or all-ones) is exactly ``batch_class_sums`` — this
    is THE oracle the weighted engines are property-tested against."""
    actions = include_actions(cfg, state)
    pol = clause_polarities(cfg)[None, :]  # [1, C]
    vote = pol if weights is None else weights.astype(jnp.int32) * pol
    return _inference_sums(cfg, actions, x, vote)


def predict_weighted(
    cfg: TMConfig, state: Array, x: Array, weights: "Array | None" = None
) -> Array:
    """Batched weighted prediction: argmax of the weighted class sums."""
    sums = batch_class_sums_weighted(cfg, state, x, weights)
    return jnp.argmax(sums, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Bitpacked inference (paper §3: 32 datapoints per machine word)
# ---------------------------------------------------------------------------

def pack_literals(x: Array) -> Array:
    """Pack the batch dim of literals into uint32 words.

    x: {0,1}[B, F] with B % 32 == 0  ->  uint32[2F, B//32]
    word bit b holds datapoint (w*32 + b).
    """
    lits = literals(x).astype(jnp.uint32)  # [B, 2F]
    B = lits.shape[0]
    assert B % 32 == 0, "batch must be a multiple of 32 for bit packing"
    lits = lits.T.reshape(lits.shape[1], B // 32, 32)  # [2F, W, 32]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(lits << shifts[None, None, :], axis=-1, dtype=jnp.uint32)


def pack_patch_literals(x: Array, sources: Array, valid: Array) -> Array:
    """Pack the literals of every patch of staged images, on the device.

    x: {0,1}[B, X] with B % 32 == 0; ``sources`` int32[F, P], where
    feature k of the patch at position p comes from: a column of ``x``,
    or X for the constant 0, or X + 1 for the constant 1 (as
    ``PatchGeometry.feature_sources`` gives them); ``valid`` uint32[P],
    all ones at the positions in use and 0 at padding.
      ->  uint32[2F, P, B//32], interleaved literals (row 2k = feature k,
    row 2k+1 its negation), both 0 at a padded position so that no
    clause fires there.  The images are packed once and the patches
    gathered as words."""
    xb = x.astype(jnp.uint32)  # [B, X]
    B = xb.shape[0]
    assert B % 32 == 0, "batch must be a multiple of 32 for bit packing"
    shifts = jnp.arange(32, dtype=jnp.uint32)
    words = jnp.sum(xb.T.reshape(xb.shape[1], B // 32, 32) << shifts,
                    axis=-1, dtype=jnp.uint32)  # [X, W]
    consts = jnp.asarray([[0], [0xFFFFFFFF]], jnp.uint32)
    words = jnp.concatenate(
        [words, jnp.broadcast_to(consts, (2, B // 32))], axis=0)
    feats = jnp.take(words, sources, axis=0)  # [F, P, W]
    keep = valid[None, :, None]
    lits = jnp.stack([feats & keep, ~feats & keep], axis=1)  # [F, 2, P, W]
    return lits.reshape(2 * sources.shape[0], *feats.shape[1:])


def unpack_bits(words: Array) -> Array:
    """uint32[..., W] -> int32[..., W*32] of {0,1}."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], words.shape[-1] * 32).astype(jnp.int32)


@partial(jax.jit, static_argnums=0)
def packed_class_sums(cfg: TMConfig, state: Array, packed_lits: Array) -> Array:
    """Bitpacked dense inference.

    packed_lits: uint32[2F, W]  ->  int32[W*32, M] class sums
    (matches ``batch_class_sums`` exactly for the packing in pack_literals).
    """
    actions = include_actions(cfg, state)  # [M, C, 2F]
    ones = jnp.uint32(0xFFFFFFFF)

    # acc[m, c, w] = AND over included k of packed_lits[k, w]
    def clause_word(a_row):  # a_row: bool[2F]
        masked = jnp.where(a_row[:, None], packed_lits, ones)  # [2F, W]
        # AND-reduce over literals via bitwise_and reduction
        return jax.lax.reduce(
            masked, ones, jnp.bitwise_and, dimensions=(0,)
        )  # [W]

    acc = jax.vmap(jax.vmap(clause_word))(actions)  # [M, C, W]
    nonempty = jnp.any(actions, axis=-1)  # [M, C]
    acc = jnp.where(nonempty[..., None], acc, jnp.uint32(0))
    bits = unpack_bits(acc)  # [M, C, B]
    pol = clause_polarities(cfg)
    sums = jnp.sum(bits * pol[None, :, None], axis=1)  # [M, B]
    return sums.T  # [B, M]


def dense_model_bytes(cfg: TMConfig) -> int:
    """Uncompressed model footprint: 1 bit per TA action."""
    return (cfg.n_tas + 7) // 8
