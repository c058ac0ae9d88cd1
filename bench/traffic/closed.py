"""Closed loop: ``clients`` callers each keep one request of ``rows`` rows
in flight in ``lane``, and send the next only when the last came back.

Parameters (``bench/traffic/<mix>.json``): ``clients``, ``rows``,
``lane``, ``pool_requests`` (distinct requests drawn from the seed, sent
in a seeded order and repeated when the run outlasts them),
``satisfy_clauses`` (clauses of the model each row is set to satisfy,
``BenchModel.rows``), ``warmup_seconds``.
One thread runs every client as an asyncio task.
"""

from __future__ import annotations

import asyncio
import itertools
import time

import numpy as np

from .common import WAIT_S, Outcomes, annotate


class Closed:
    def __init__(self, params: dict, model, rng: np.random.Generator):
        self.clients = int(params["clients"])
        self.rows = int(params["rows"])
        self.lane = params["lane"]
        self.warmup_seconds = float(params["warmup_seconds"])
        n = int(params["pool_requests"])
        x = model.rows(n * self.rows, int(params["satisfy_clauses"]), rng)
        self.pools = [x.reshape(n, self.rows, -1)]
        self._order = itertools.cycle(rng.permutation(n).tolist())

    def schedule(self, n_requests: int) -> list:
        """Pool indices of the next ``n_requests`` sends, in order; each
        call continues where the last ended."""
        return [next(self._order) for _ in range(n_requests)]

    def run(self, acc, slot: str, seconds: float, trace: bool = False):
        return asyncio.run(self._run(acc, slot, seconds, trace))

    async def _run(self, acc, slot, seconds, trace):
        pool = self.pools[0]
        out = Outcomes()
        t_start = time.perf_counter()
        t_end = t_start + seconds

        async def client():
            while time.perf_counter() < t_end:
                idx = next(self._order)
                sent = time.perf_counter()
                with annotate(trace, "bench.submit"):
                    h = acc.submit(slot, pool[idx], priority=self.lane)
                try:
                    await h.async_result(WAIT_S)
                except Exception:  # noqa: BLE001 - a failure is recorded
                    pass
                out.add(0, idx, np.nan, sent, h)

        await asyncio.gather(*(client() for _ in range(self.clients)))
        return out.records(t_start, t_end)


def build(params, model, rng):
    return Closed(params, model, rng)
