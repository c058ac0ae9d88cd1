"""The plain reference of ``seam_model``: the TM reference over the first
F features of each row, F from the truth's literal width
(``test_bench_seam.py`` installs it as ``bench.configs.seam_reference``)."""

import numpy as np

from bench.configs import tm_reference

drop_last_include = tm_reference.drop_last_include
predictions = tm_reference.predictions


def class_sums(truth: np.ndarray, x: np.ndarray,
               block_rows: int = tm_reference.BLOCK_ROWS) -> np.ndarray:
    f = truth.shape[-1] // 2
    return tm_reference.class_sums(truth, np.asarray(x)[:, :f], block_rows)
