"""Host time per batch in batch formation and shedding (``tm.form``): the
program's span summed over the traced window, over the ``tm.batch`` spans
there (``bench/span_reduce.py``)."""

from bench import span_reduce


def read(run):
    return span_reduce.ms_per_batch(run, "tm.form")
