"""Rows served over the engine rows run, padding included, in the window
(``ServeMetrics.rows / padded_rows``)."""


def read(run):
    c = run.counters
    if not c["padded_rows"]:
        return None
    return 100.0 * c["rows"] / c["padded_rows"]
