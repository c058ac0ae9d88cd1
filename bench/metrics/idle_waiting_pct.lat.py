"""Share of the traced window in which the device idled while the
scheduler loop sat in ``tm.wait`` with no slot due: that part of the
exact idle split of ``bench/span_reduce.py``."""

from bench import span_reduce


def read(run):
    s = span_reduce.for_run(run)
    if not s or not s["idle_split_s"]:
        return None
    return 100.0 * s["idle_split_s"]["tm.wait"] / s["window_s"]
