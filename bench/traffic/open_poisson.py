"""Open loop: Poisson arrivals at ``rate_per_s``, sent on schedule
whether or not earlier requests came back.

Parameters (``bench/traffic/<mix>.json``): ``rate_per_s``; ``mix``, a
list of request classes, each with ``share`` (of requests), ``rows``,
``lane`` and ``pool_requests`` (distinct requests drawn from the seed);
``satisfy_clauses`` (see ``closed.py``); ``warmup_seconds``.
Each request is timed from when it was due; ``Records.lag`` holds how
late the generator sent it.
"""

from __future__ import annotations

import time

import numpy as np

from .common import WAIT_S, Outcomes, annotate, is_terminal, wait_all

LEAD_S = 0.005  # the first arrival is due this long after the run starts


class OpenPoisson:
    def __init__(self, params: dict, model, rng: np.random.Generator):
        self.rate = float(params["rate_per_s"])
        self.warmup_seconds = float(params["warmup_seconds"])
        self.mix = params["mix"]
        self.share = np.cumsum([float(c["share"]) for c in self.mix])
        if not np.isclose(self.share[-1], 1.0):
            raise ValueError(f"mix shares sum to {self.share[-1]}, not 1")
        satisfy = int(params["satisfy_clauses"])
        self.pools = [
            model.rows(int(c["pool_requests"]) * int(c["rows"]), satisfy, rng)
            .reshape(int(c["pool_requests"]), int(c["rows"]), -1)
            for c in self.mix
        ]
        self.rng = rng

    def schedule(self, seconds: float):
        """(due offsets, class, pool index) of every arrival in
        ``[0, seconds)``, drawn from this traffic's stream."""
        n_max = int(self.rate * seconds * 1.5) + 64
        due = np.cumsum(self.rng.exponential(1.0 / self.rate, n_max))
        while due[-1] < seconds:  # rare: draw more arrivals
            more = self.rng.exponential(1.0 / self.rate, n_max)
            due = np.concatenate([due, due[-1] + np.cumsum(more)])
        due = due[due < seconds]
        kind = np.searchsorted(self.share, self.rng.random(due.size),
                               side="right")
        sizes = np.array([p.shape[0] for p in self.pools])
        index = (self.rng.random(due.size) * sizes[kind]).astype(np.int64)
        return due, kind, index

    def run(self, acc, slot: str, seconds: float, trace: bool = False):
        due, kind, index = self.schedule(seconds)
        lanes = [c["lane"] for c in self.mix]
        out, pending, lag = Outcomes(), [], np.empty(due.size)
        t_start = time.perf_counter() + LEAD_S
        for i in range(due.size):
            at = t_start + due[i]
            if at > time.perf_counter():
                pending = _collect(pending, out)  # while there is time
                wait = at - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
            k, idx = int(kind[i]), int(index[i])
            sent = time.perf_counter()
            lag[i] = sent - at
            with annotate(trace, "bench.submit"):
                h = acc.submit(slot, self.pools[k][idx], priority=lanes[k])
            pending.append((k, idx, at, sent, h))
        t_end = t_start + seconds
        wait_all([p[4] for p in pending], t_end + WAIT_S)
        for p in pending:
            out.add(*p)
        return out.records(t_start, t_end, lag=lag)


def _collect(pending, out):
    """Add the outcomes of terminal requests from ``pending`` to ``out``;
    -> the requests still pending."""
    still = []
    for p in pending:
        if is_terminal(p[4]):
            out.add(*p)
        else:
            still.append(p)
    return still


def build(params, model, rng):
    return OpenPoisson(params, model, rng)
