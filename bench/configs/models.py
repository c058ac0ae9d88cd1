"""The default model module: a plain TM ``(classes, clauses, features)``
whose include actions are drawn from the run's seed, and what the
benchmark needs to know of it.

A configuration names its model module under ``"model"``
(``bench/configs/<module>.py``; this one when the key is absent).  The
module's ``build(config, seed)`` returns a ``BenchModel``: what the
reference is given, what the program serves, how request rows are drawn,
the work a batch needs, and the trace patterns of its kernels.  The
harness, the generators and the metric readers know a model only through
it.

Kept with the benchmark so that no change to the program can move the
yardstick.  The ``literal`` rule is ``benchmarks/tm_bench_common.py``'s
``synthetic_mnist_scale`` with its Bernoulli draw replaced by an exact
count: every seed then yields the same instruction count, and so the same
compiled shapes.

Work per datapoint: one AND per include and one add per clause.  Per
batch: the uint16 include stream is read once; per row, the packed
literals (2F bits) come in and M int32 class sums go out.  The count is
taken from the model and the rows served, whatever implements it (never
from a kernel's padded layout).
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Callable, Dict

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent
MODEL_STREAM = 1  # default_rng([seed, MODEL_STREAM]) draws the includes
PLANT_CHUNK = 8192  # rows per vectorised planting step
# the popcount kernel's device events: on the serving path it is the only
# Pallas (Mosaic) kernel, and its trace events carry no name of their own
POPCOUNT = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class BenchModel:
    """A configuration's model as the benchmark sees it.

    ``truth`` is what the configuration's reference is given;
    ``program_model`` the ``CompressedModel`` the program serves;
    ``rows(n_rows, satisfy, rng)`` draws uint8 request rows;
    ``ops(rows)`` and ``bytes(rows, batches)`` count the work of serving
    ``rows`` datapoints in ``batches`` calls; ``kernels`` maps a kernel's
    name to the pattern its device events carry in a trace."""

    truth: np.ndarray
    program_model: object
    rows: Callable[[int, int, np.random.Generator], np.ndarray]
    ops: Callable[[int], int]
    bytes: Callable[[int, int], float]
    kernels: Dict[str, str]


def load_config(name: str) -> dict:
    """The configuration ``bench/configs/<name>.json``."""
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def include_actions(config: dict, seed: int) -> np.ndarray:
    """bool[M, C, 2F] include actions, literal slot 2k = x_k and slot
    2k+1 = NOT x_k, with exactly ``n_includes`` True."""
    m, c, f = config["n_classes"], config["n_clauses"], config["n_features"]
    n = config["n_includes"]
    rng = np.random.default_rng([seed, MODEL_STREAM])
    acts = np.zeros((m, c, 2 * f), bool)
    rule = config["include_rule"]
    if rule == "literal":
        acts.reshape(-1)[rng.choice(m * c * 2 * f, n, replace=False)] = True
    elif rule == "feature":
        pairs = rng.choice(m * c * f, n, replace=False)
        negated = rng.integers(0, 2, n)
        clause, feature = np.divmod(pairs, f)
        acts.reshape(m * c, 2 * f)[clause, 2 * feature + negated] = True
    else:
        raise ValueError(f"unknown include_rule {rule!r}")
    return acts


def make_rows(actions: np.ndarray, n_rows: int, satisfy: int,
              rng: np.random.Generator) -> np.ndarray:
    """uint8[n_rows, F]: uniform random bits, then each row set to satisfy
    ``satisfy`` clauses drawn at random from the model, as rows of a
    model's own distribution make its clauses fire."""
    m, c, l2 = actions.shape
    x = rng.integers(0, 2, (n_rows, l2 // 2), dtype=np.uint8)
    if satisfy <= 0:
        return x
    flat = actions.reshape(m * c, l2)
    clauses = np.flatnonzero(flat.any(axis=1))
    width = int(flat.sum(axis=1).max())
    slots = np.zeros((clauses.size, width), np.int64)
    valid = np.zeros((clauses.size, width), bool)
    for q, clause in enumerate(clauses):
        ks = np.flatnonzero(flat[clause])
        slots[q, :ks.size], valid[q, :ks.size] = ks, True
    pick = rng.integers(0, clauses.size, (n_rows, satisfy))
    for lo in range(0, n_rows, PLANT_CHUNK):
        p = pick[lo:lo + PLANT_CHUNK]
        rows = np.broadcast_to(
            np.arange(lo, lo + p.shape[0])[:, None, None],
            (p.shape[0], satisfy, width),
        )
        ok = valid[p]
        k = slots[p][ok]
        # slot 2f is feature f, slot 2f+1 its negation
        x[rows[ok], k >> 1] = 1 - (k & 1)
    return x


def serve_ops(rows: int, n_includes: int, n_clauses: int) -> int:
    """Operations to answer ``rows`` datapoints; ``n_clauses`` counts the
    clauses of every class."""
    return rows * (n_includes + n_clauses)


def serve_bytes(rows: int, batches: int, n_includes: int, n_features: int,
                n_classes: int) -> float:
    """Bytes moved to answer ``rows`` datapoints in ``batches`` calls."""
    return (batches * 2 * n_includes
            + rows * (2 * n_features / 8 + n_classes * 4))


def build(config: dict, seed: int) -> BenchModel:
    """The plain TM of ``config``, its includes drawn from ``seed``, encoded
    through the program's public ``encode``."""
    from repro.core import TMConfig
    from repro.core.compress import encode

    m, c, f = config["n_classes"], config["n_clauses"], config["n_features"]
    n = config["n_includes"]
    actions = include_actions(config, seed)
    model = encode(TMConfig(n_classes=m, n_clauses=c, n_features=f), actions)
    return BenchModel(
        truth=actions,
        program_model=model,
        rows=lambda n_rows, satisfy, rng: make_rows(actions, n_rows, satisfy,
                                                    rng),
        ops=lambda rows: serve_ops(rows, n, m * c),
        bytes=lambda rows, batches: serve_bytes(rows, batches, n, f, m),
        kernels={"popcount": POPCOUNT},
    )
