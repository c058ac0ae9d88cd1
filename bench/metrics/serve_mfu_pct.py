"""The whole serving step's share of the chip's peak: rows completed per
second in the window times the operations a row needs (the model's
``ops``, ``bench/configs/``) over the int8 peak (``bench/peaks.json``)."""


def read(run):
    if run.peak is None:
        return None
    r = run.records
    done = ~r.failed & (r.done >= r.t_start) & (r.done <= r.t_end)
    rows_per_s = float(r.rows[done].sum()) / run.window_s
    return 100.0 * rows_per_s * run.model.ops(1) / run.peak["int8_ops_per_s"]
