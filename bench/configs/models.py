"""Include actions for a configuration, drawn from the run's seed.

Kept with the benchmark so that no change to the program can move the
yardstick.  The ``literal`` rule is ``benchmarks/tm_bench_common.py``'s
``synthetic_mnist_scale`` with its Bernoulli draw replaced by an exact
count: every seed then yields the same instruction count, and so the same
compiled shapes.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent
MODEL_STREAM = 1  # default_rng([seed, MODEL_STREAM]) draws the includes


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
