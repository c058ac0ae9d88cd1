"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

The traced window is the host span named ``window`` that the harness
opens around it, on the trace's own clock.  For every device plane the
operations are the events of its ``XLA Ops`` line; a plane without one is no
device.  Busy time is the union of those intervals inside the window,
averaged over devices; a kernel's time is the sum of the durations of the
events whose name contains its pattern.  Each idle gap between device
operations is put down to the host span that overlaps it most (the
innermost on a tie), or to ``NO_SPAN``.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

NO_SPAN = "(no host span)"
OPS_LINE = "XLA Ops"
TOP = 10  # entries kept in each list of the breakdown
SHORT_NS = 10e6  # host spans up to this long are found by bisection


def find_trace(log_dir) -> Path:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _device_ops(plane) -> List[Tuple[str, float, float]]:
    return [(ev.name, ev.start_ns, ev.end_ns)
            for ln in plane.lines if ln.name == OPS_LINE
            for ev in ln.events]


def reduce_trace(path, window: str, kernel_patterns: Iterable[str] = ()) -> Dict:
    """-> ``window_s``, ``busy_s`` (mean over devices), ``devices``,
    ``kernel_s`` (pattern -> seconds summed over devices),
    ``device_ops`` and ``idle_gaps`` ([name, seconds], longest first)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = list(data.planes)
    host = [ev for p in planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events]
    windows = [ev for ev in host if ev.name == window]
    if not windows:
        raise ValueError(f"no {window!r} span in {path}")
    w = max(windows, key=lambda ev: ev.duration_ns)
    lo, hi = w.start_ns, w.end_ns
    spans = sorted(
        (ev.start_ns, ev.end_ns, ev.name) for ev in host
        if ev.name != window and ev.duration_ns > 0
        and ev.end_ns > lo and ev.start_ns < hi
    )
    short = [sp for sp in spans if sp[1] - sp[0] <= SHORT_NS]
    long_ = [sp for sp in spans if sp[1] - sp[0] > SHORT_NS]
    starts = [sp[0] for sp in short]

    devices = [p for p in planes
               if any(ln.name == OPS_LINE for ln in p.lines)]
    patterns = list(kernel_patterns)
    kernel_s = {pat: 0.0 for pat in patterns}
    op_s: Dict[str, float] = defaultdict(float)
    gap_s: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for plane in devices:
        ops = [(n, s, e) for n, s, e in _device_ops(plane)
               if e > lo and s < hi]
        for name, s, e in ops:
            op_s[name] += (e - s) / 1e9
            for pat in patterns:
                if pat in name:
                    kernel_s[pat] += (e - s) / 1e9
        merged = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gap_s[_host_span(short, starts, long_, g0, g1)] += (
                    (g1 - g0) / 1e9)
    n_dev = max(len(devices), 1)
    top = lambda d: sorted(  # noqa: E731
        ([k, v / n_dev] for k, v in d.items()), key=lambda kv: -kv[1]
    )[:TOP]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n_dev,
        "devices": len(devices),
        "kernel_s": kernel_s,
        "device_ops": top(op_s),
        "idle_gaps": top(gap_s),
    }


def _host_span(short, starts, long_, g0, g1) -> str:
    """The host span overlapping ``[g0, g1)`` most (innermost on a tie).
    ``short`` spans (sorted, their starts in ``starts``) last at most
    ``SHORT_NS``; the few ``long_`` ones are scanned whole."""
    i = bisect.bisect_left(starts, g0 - SHORT_NS)
    j = bisect.bisect_left(starts, g1)
    best, best_key = NO_SPAN, (0.0, 0.0)
    for s, e, name in short[i:j] + long_:
        overlap = min(e, g1) - max(s, g0)
        key = (overlap, -(e - s))
        if overlap > 0 and key > best_key:
            best, best_key = name, key
    return best
