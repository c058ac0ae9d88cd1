"""The work a TM serving batch needs, counted from the model and the rows
served, whatever implements it (never from a kernel's padded layout),
and the chip's peaks it is measured against.

Per datapoint: one AND per include and one add per clause.  Per batch:
the uint16 include stream is read once; per row, the packed literals
(2F bits) come in and M int32 class sums go out.

The v5e publishes no peak for bitwise integer work; its int8 peak, the
only integer peak it publishes, stands as the ceiling for the ANDs and
adds counted here.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def serve_ops(rows: int, n_includes: int, n_clauses: int) -> int:
    """Operations to answer ``rows`` datapoints; ``n_clauses`` counts the
    clauses of every class."""
    return rows * (n_includes + n_clauses)


def serve_bytes(rows: int, batches: int, n_includes: int, n_features: int,
                n_classes: int) -> float:
    """Bytes moved to answer ``rows`` datapoints in ``batches`` calls."""
    return (batches * 2 * n_includes
            + rows * (2 * n_features / 8 + n_classes * 4))


def peak(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def roofline(ops: float, nbytes: float, seconds: float, pk: dict):
    """(share of the roofline in %, the bound that applies: 'ops' or
    'bytes') for work done in ``seconds``: the least time the chip could
    take over the time taken."""
    t_ops = ops / pk["int8_ops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return 100.0 * max(t_ops, t_bytes) / seconds, (
        "ops" if t_ops >= t_bytes else "bytes")
