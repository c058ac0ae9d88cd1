"""The popcount kernel's share of its roofline in the traced window: the
least time the chip needs for the work served (``bench/work.py``, from
the model and the rows, at the peaks of ``bench/peaks.json``) over the
summed device time of the kernel's events."""

from bench import work
from bench.harness import KERNEL


def read(run):
    t = run.trace
    if t is None or run.peak is None or not t["kernel_s"].get(KERNEL):
        return None
    cfg, c = run.config, run.counters
    ops = work.serve_ops(c["rows"], cfg["n_includes"],
                         cfg["n_classes"] * cfg["n_clauses"])
    nbytes = work.serve_bytes(c["rows"], c["batches"], cfg["n_includes"],
                              cfg["n_features"], cfg["n_classes"])
    pct, _ = work.roofline(ops, nbytes, t["kernel_s"][KERNEL], run.peak)
    return pct
