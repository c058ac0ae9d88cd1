"""Host time per batch in argmax, demux and the recompile check (``tm.demux``): the
program's span summed over the traced window, over the ``tm.batch`` spans
there (``bench/span_reduce.py``)."""

from bench import span_reduce


def read(run):
    return span_reduce.ms_per_batch(run, "tm.demux")
