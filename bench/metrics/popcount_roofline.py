"""The popcount kernel's share of its roofline in the traced window: the
least time the chip needs for the work served (the model's ``ops`` and
``bytes``, ``bench/configs/``, at the peaks of ``bench/peaks.json``)
over the summed device time of the events that match the model's
``popcount`` kernel."""

from bench import work


def read(run):
    t, pattern = run.trace, run.model.kernels.get("popcount")
    if t is None or run.peak is None or not t["kernel_s"].get(pattern):
        return None
    c = run.counters
    pct, _ = work.roofline(run.model.ops(c["rows"]),
                           run.model.bytes(c["rows"], c["batches"]),
                           t["kernel_s"][pattern], run.peak)
    return pct
