"""Host time in the engine per batch (``ServeMetrics.engine_s``, which
blocks on the device's answer) over the batches of the window."""


def read(run):
    c = run.counters
    if not c["batches"]:
        return None
    return 1e3 * c["engine_s"] / c["batches"]
