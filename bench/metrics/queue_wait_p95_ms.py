"""95th percentile of the scheduler's queue wait (enqueue to the first
batch a request's rows enter), over the requests completed in the run."""

import numpy as np


def read(run):
    q = run.records.queue_delay[~run.records.failed]
    q = q[~np.isnan(q)]
    if not q.size:
        return None
    return float(np.percentile(q, 95)) * 1e3
