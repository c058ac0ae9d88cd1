"""From the start of a batch's ``tm.launch`` span to the start of its first
device op, the median over the batches of the traced window
(``bench/span_reduce.py``)."""

from bench import span_reduce


def read(run):
    s = span_reduce.for_run(run)
    if not s or not s["launch_lag"]:
        return None
    return s["launch_lag"]["p50_ms"]
