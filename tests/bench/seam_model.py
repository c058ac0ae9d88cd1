"""A model module that is more than ``(classes, clauses, features)``, for
the seam tests (``test_bench_seam.py`` installs it as
``bench.configs.seam_model``).

Its truth is the default include actions over F features.  The program
serves them encoded over ``PAD`` more features, with nothing included
there, under the configuration's ``threshold``; its rows carry those
features as random bits, and its reference (``seam_reference``) reads
only the first F.  Its work count and kernel pattern are its own.
"""

from __future__ import annotations

import numpy as np

from bench.configs import models

PAD = 8  # features the program sees beyond the truth's
KERNEL = "seam_kernel"


def serve_ops(config: dict, rows: int) -> int:
    """Every include, clause and padded feature, once per row."""
    m, c, f = config["n_classes"], config["n_clauses"], config["n_features"]
    return rows * (config["n_includes"] + m * c + f + PAD)


def serve_bytes(config: dict, rows: int, batches: int) -> float:
    """The stream twice a batch; the wide rows in, the sums out."""
    f, m = config["n_features"] + PAD, config["n_classes"]
    return batches * 4 * config["n_includes"] + rows * (f + m * 4)


def build(config: dict, seed: int) -> models.BenchModel:
    from repro.core import TMConfig
    from repro.core.compress import encode

    truth = models.include_actions(config, seed)
    m, c, l2 = truth.shape
    wide = np.zeros((m, c, l2 + 2 * PAD), bool)
    wide[..., :l2] = truth
    cfg = TMConfig(n_classes=m, n_clauses=c, n_features=l2 // 2 + PAD,
                   threshold=config["threshold"])

    def rows(n_rows, satisfy, rng):
        x = models.make_rows(truth, n_rows, satisfy, rng)
        pad = rng.integers(0, 2, (n_rows, PAD), dtype=np.uint8)
        return np.concatenate([x, pad], axis=1)

    return models.BenchModel(
        truth=truth,
        program_model=encode(cfg, wide),
        rows=rows,
        ops=lambda n_rows: serve_ops(config, n_rows),
        bytes=lambda n_rows, batches: serve_bytes(config, n_rows, batches),
        kernels={"popcount": KERNEL},
    )
