"""Device time of the model's ``popcount`` kernel per batch: the summed
duration of its events in the traced window over the window's batches.
Beside ``engine_ms_per_batch`` it says how much of a batch's cycle the
kernel is."""


def read(run):
    t, pattern = run.trace, run.model.kernels.get("popcount")
    c = run.counters
    if t is None or not c["batches"] or not t["kernel_s"].get(pattern):
        return None
    return 1e3 * t["kernel_s"][pattern] / c["batches"]
