"""Process start to the first timed request: imports, models and traffic
from the seed, the envelope, the compile (from the cache after a cell's
first run) and the warm-up."""


def read(run):
    return run.setup_s
