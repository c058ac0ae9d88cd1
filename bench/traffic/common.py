"""What every traffic generator shares: the record of what a run sent and
what came back.  Request rows come from the model
(``BenchModel.rows``, ``bench/configs/``)."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent
TRAFFIC_STREAM = 2  # default_rng([seed, TRAFFIC_STREAM]) draws the traffic
WAIT_S = 60.0  # an answer may come this long after the window closes


def load_traffic(name: str) -> dict:
    """The traffic mix ``bench/traffic/<name>.json``."""
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


class Outcomes:
    """What each request's handle says, taken as soon as it is terminal.

    Kept as columns of scalars and arrays, which the garbage collector
    does not track: a run that kept a handle or a tuple per request would
    push the process into full collections (about 0.1 s each, a pause for
    the server too) as the window went on."""

    COLUMNS = ("pool", "index", "due", "sent", "queue_delay", "done",
               "failed", "sums", "preds", "rows")

    def __init__(self):
        for c in self.COLUMNS:
            setattr(self, c, [])

    def add(self, pool: int, index: int, due: float, sent: float, h) -> None:
        ok = h.completed_at is not None and not h.failed
        q = h.queue_delay_s
        self.pool.append(pool)
        self.index.append(index)
        self.due.append(due)
        self.sent.append(sent)
        self.queue_delay.append(np.nan if q is None else q)
        self.done.append(h.completed_at if ok else np.nan)
        self.failed.append(not ok)
        self.sums.append(h.class_sums if ok else None)
        self.preds.append(h.predictions if ok else None)
        self.rows.append(h.n_rows)

    def records(self, t_start: float, t_end: float, lag=None) -> "Records":
        order = np.argsort(np.asarray(self.sent, float), kind="stable")
        col = lambda c, t: np.asarray(getattr(self, c), t)[order]  # noqa: E731
        pick = lambda c: [getattr(self, c)[i] for i in order]  # noqa: E731
        return Records(col("pool", np.int32), col("index", np.int64),
                       col("due", float), col("sent", float),
                       col("queue_delay", float), col("done", float),
                       col("failed", bool), pick("sums"), pick("preds"),
                       col("rows", np.int64), t_start, t_end, lag)


def is_terminal(h) -> bool:
    """Answered (``completed_at`` is stamped after the last row is
    written), failed or shed."""
    return h.completed_at is not None or h.failed or h.expired


@dataclasses.dataclass
class Records:
    """One run of a generator, in send order: per request, the pool it
    came from, its index there, when it was due (NaN in a closed loop)
    and sent, how long it queued before its first batch, when it
    completed (NaN where it never did), and what came back."""

    pool: np.ndarray
    index: np.ndarray
    due: np.ndarray
    sent: np.ndarray
    queue_delay: np.ndarray
    done: np.ndarray
    failed: np.ndarray
    sums: List[Optional[np.ndarray]]
    preds: List[Optional[np.ndarray]]
    rows: np.ndarray
    t_start: float
    t_end: float
    lag: Optional[np.ndarray] = None  # open loop: sent - due, per request


def wait_all(handles, deadline: float) -> None:
    """Block until every handle is terminal or ``deadline`` passes; a
    handle that is still pending then counts as failed."""
    for h in handles:
        left = deadline - time.perf_counter()
        if left <= 0:
            return
        with contextlib.suppress(Exception):
            h.wait(left)


def annotate(on: bool, name: str):
    """A profiler span around a call into the program, when tracing."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)
