"""95th percentile of how late the load generator sent a request against
its schedule: a starved generator is not a fast server."""

import numpy as np


def read(run):
    lag = run.records.lag
    if lag is None or not lag.size:
        return None
    return float(np.percentile(lag, 95)) * 1e3
