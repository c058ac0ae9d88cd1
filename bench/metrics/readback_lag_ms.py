"""From the end of a batch's last device op to the end of its ``tm.d2h``
span, the median over the batches of the traced window
(``bench/span_reduce.py``)."""

from bench import span_reduce


def read(run):
    s = span_reduce.for_run(run)
    if not s or not s["readback_lag"]:
        return None
    return s["readback_lag"]["p50_ms"]
