"""Rows of the requests completed inside the window, over its seconds."""


def read(run):
    r = run.records
    done = ~r.failed & (r.done >= r.t_start) & (r.done <= r.t_end)
    return float(r.rows[done].sum()) / run.window_s
