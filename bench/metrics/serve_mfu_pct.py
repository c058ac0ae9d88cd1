"""The whole serving step's share of the chip's peak: rows completed per
second in the window times the operations a row needs
(``bench/work.py``) over the int8 peak (``bench/peaks.json``)."""

from bench import work


def read(run):
    if run.peak is None:
        return None
    r, cfg = run.records, run.config
    done = ~r.failed & (r.done >= r.t_start) & (r.done <= r.t_end)
    rows_per_s = float(r.rows[done].sum()) / run.window_s
    ops_per_row = work.serve_ops(1, cfg["n_includes"],
                                 cfg["n_classes"] * cfg["n_clauses"])
    return 100.0 * rows_per_s * ops_per_row / run.peak["int8_ops_per_s"]
