"""The model module of a Convolutional TM configuration (Granmo et al.,
"The Convolutional Tsetlin Machine", arXiv:1905.09688), as
``bench/configs/models.py`` is of a plain TM.

The configuration gives the geometry (``image_h x image_w`` images read
through a ``window_h x window_w`` window at every position) and the
clauses; the include actions over the features of one patch are drawn
from the seed by the default module's rule.  The reference
(``ctm_reference``) is given them with the geometry; the program serves
them encoded through its public ``encode`` under a ``TMConfig`` whose
``patch`` is that geometry.

Request rows are images: uniform random bits, then each row set to
satisfy ``satisfy`` clauses drawn at random from those that can fire,
each planted at a random position its coordinate includes admit.

Work per image: one AND per include and one OR per clause at every
position, and one add per clause.  Per batch: the uint16 include stream
is read once; per row, the image's bits come in and M int32 class sums
go out.  The count is taken from the model and the rows served (never
from a kernel's padded layout).
"""

from __future__ import annotations

import numpy as np

from bench.configs import models
from bench.configs.ctm_reference import Truth

# the popcount kernel's device events: the trace names each event after
# the kernel's HLO instruction, ``%tm_popcount.<n> = ...``
POPCOUNT = "%tm_popcount.1 = "
PLANT_CHUNK = 4096  # rows per vectorised planting step


def truth_of(config: dict, seed: int) -> Truth:
    """The include actions of ``config`` drawn from ``seed``, with the
    geometry they are read in."""
    return Truth(actions=models.include_actions(config, seed),
                 image=(config["image_h"], config["image_w"]),
                 window=(config["window_h"], config["window_w"]))


def plantable(truth: Truth):
    """For every clause that can fire: its index in ``actions`` flattened
    over (class, clause), the range of positions its coordinate includes
    admit (``lo``, ``hi`` as [y, x], inclusive), and its window includes
    as (pixel offset, value) padded to the widest clause."""
    acts = truth.actions
    m, c, l2 = acts.shape
    (h_img, w_img), (h, w) = truth.image, truth.window
    n_pix, ny = h * w, h_img - h
    flat = acts.reshape(m * c, l2)
    q, k = np.nonzero(flat)
    feature, negated = k >> 1, k & 1
    py, px = truth.positions
    lo = np.zeros((m * c, 2), np.int64)
    hi = np.tile(np.array([py - 1, px - 1]), (m * c, 1))
    for axis, first, n in ((0, n_pix, ny), (1, n_pix + ny, w_img - w)):
        sel = (feature >= first) & (feature < first + n)
        t = feature[sel] - first
        # bit t of the coordinate is 1 iff it is greater than t
        pos = negated[sel] == 0
        np.maximum.at(lo[:, axis], q[sel][pos], t[pos] + 1)
        np.minimum.at(hi[:, axis], q[sel][~pos], t[~pos])
    ok = flat.any(axis=1) & (lo <= hi).all(axis=1)
    pix = feature < n_pix
    dy, dx = np.divmod(feature[pix], w)
    offset = dy * w_img + dx
    counts = np.bincount(q[pix], minlength=m * c)
    width = max(int(counts.max()), 1)
    slots = np.zeros((m * c, width), np.int64)
    value = np.zeros((m * c, width), np.uint8)
    used = np.zeros((m * c, width), bool)
    col = np.arange(q[pix].size) - np.repeat(
        np.cumsum(counts) - counts, counts)
    slots[q[pix], col] = offset
    value[q[pix], col] = 1 - negated[pix]
    used[q[pix], col] = True
    keep = np.flatnonzero(ok)
    return keep, lo[keep], hi[keep], slots[keep], value[keep], used[keep]


def make_images(truth: Truth, n_rows: int, satisfy: int,
                rng: np.random.Generator) -> np.ndarray:
    """uint8[n_rows, H*W]: random bits, each row then set to satisfy
    ``satisfy`` clauses drawn at random, each at a random position in the
    range its coordinate includes admit."""
    h_img, w_img = truth.image
    x = rng.integers(0, 2, (n_rows, h_img * w_img), dtype=np.uint8)
    if satisfy <= 0:
        return x
    _, lo, hi, slots, value, used = plantable(truth)
    pick = rng.integers(0, lo.shape[0], (n_rows, satisfy))
    u = rng.random((n_rows, satisfy, 2))
    for start in range(0, n_rows, PLANT_CHUNK):
        p = pick[start:start + PLANT_CHUNK]
        span = hi[p] - lo[p] + 1
        yx = lo[p] + (u[start:start + p.shape[0]] * span).astype(np.int64)
        corner = yx[..., 0] * w_img + yx[..., 1]  # [rows, satisfy]
        rows = np.broadcast_to(
            np.arange(start, start + p.shape[0])[:, None, None],
            slots[p].shape)
        ok = used[p]
        x[rows[ok], (corner[..., None] + slots[p])[ok]] = value[p][ok]
    return x


def serve_ops(rows: int, positions: int, n_includes: int,
              n_clauses: int) -> int:
    """Operations to answer ``rows`` images; ``n_clauses`` counts the
    clauses of every class."""
    return rows * positions * (n_includes + n_clauses) + rows * n_clauses


def serve_bytes(rows: int, batches: int, n_includes: int, input_width: int,
                n_classes: int) -> float:
    """Bytes moved to answer ``rows`` images in ``batches`` calls."""
    return batches * 2 * n_includes + rows * (input_width / 8 + n_classes * 4)


def build(config: dict, seed: int) -> models.BenchModel:
    """The Convolutional TM of ``config``, its includes drawn from
    ``seed``, encoded through the program's public ``encode``."""
    from repro.core import TMConfig
    from repro.core.compress import encode
    from repro.core.tm import PatchGeometry

    geom = PatchGeometry(config["image_h"], config["image_w"],
                         config["window_h"], config["window_w"])
    if (geom.n_features, geom.n_positions, geom.input_width) != (
            config["n_features"], config["n_positions"],
            config["input_width"]):
        raise ValueError(
            f"{config['name']}: the geometry gives {geom.n_features} "
            f"features, {geom.n_positions} positions and "
            f"{geom.input_width} input bits; the configuration states "
            f"{config['n_features']}, {config['n_positions']} and "
            f"{config['input_width']}")
    m, c, n = config["n_classes"], config["n_clauses"], config["n_includes"]
    truth = truth_of(config, seed)
    cfg = TMConfig(n_classes=m, n_clauses=c, n_features=geom.n_features,
                   patch=geom)
    return models.BenchModel(
        truth=truth,
        program_model=encode(cfg, truth.actions),
        rows=lambda n_rows, satisfy, rng: make_images(truth, n_rows, satisfy,
                                                      rng),
        ops=lambda rows: serve_ops(rows, geom.n_positions, n, m * c),
        bytes=lambda rows, batches: serve_bytes(rows, batches, n,
                                                geom.input_width, m),
        kernels={"popcount": POPCOUNT},
    )
