"""95th percentile of request latency, from each request's due time in
the schedule to its completion, over every request due in the window; a
request that failed or never came back counts as infinitely late."""

import numpy as np


def read(run):
    r = run.records
    if np.isnan(r.due).all():
        return None  # a closed loop has no schedule to be late against
    lat = np.where(r.failed, np.inf, r.done - r.due)
    return float(np.percentile(lat, 95, method="higher")) * 1e3
