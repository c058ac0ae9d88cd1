"""The chip's peaks, and a kernel's share of its roofline.

The work a batch needs comes from the configuration's model module
(``BenchModel.ops`` and ``BenchModel.bytes``, ``bench/configs/``).

The v5e publishes no peak for bitwise integer work; its int8 peak, the
only integer peak it publishes, stands as the ceiling for the ANDs and
adds the TM models count.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


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
