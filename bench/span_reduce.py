"""Reduce the serving path's own spans in a profiler trace against the
device's events.

The program opens one host span per phase of its batch body; their names
are listed here (``PHASES``), so the benchmark imports nothing of the
program to find them.  Inside the window span the harness opens, this
gives:

* per phase: count, total seconds and p50/p99/min/max ms.  A span counts
  where it lies wholly inside the window; a phase of a batch counts
  where its ``tm.batch`` does, so each phase counts once per batch;
* the device clock's offset.  The trace places device events on the host
  clock, but not exactly: on a TPU v5e each execution of the serving step
  shows 0.3-1.2 ms before the host enqueued it, an offset that differs
  from one process to the next and can move in a trace's first second.
  The profiler links each execution (an ``XLA Modules`` event) to the
  host event that enqueued it by a flow id (``_c`` on the one, ``_p`` on
  the other).  Near each execution the
  offset is the least shift that puts no execution enqueued within
  ``OFFSET_WINDOW_S`` of it before its enqueue; every device time below
  is shifted by the offset of the execution that began last before it;
* per batch: the executions enqueued inside its ``tm.batch`` belong to
  it.  ``launch_lag`` is the start of the first less the start of the
  ``tm.launch``; ``readback_lag`` is the end of its ``tm.d2h`` less the
  end of the last;
* an exact split of the device's idle time: the window less the union of
  each device's ``XLA Ops``, cut along the scheduler thread's leaf phases
  (``LEAVES``).  Idle time inside a ``tm.batch`` but outside its phases is
  ``batch_other``; outside every span it is ``loop``.  The parts sum to
  the idle time, averaged over devices as busy time is.

The scheduler thread is the trace line that holds the most ``tm.batch``
spans.  A trace without a device plane (the CPU's), or without the
enqueue flows, gives the phases and no offset, lags or split.

    python3 bench/span_reduce.py [trace dir or .xplane.pb]
"""

from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict, deque
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench.trace_reduce import OPS_LINE, _clip, _union, find_trace  # noqa: E402

BATCH = "tm.batch"
WAIT = "tm.wait"
STEPS = ("tm.form", "tm.h2d", "tm.launch", "tm.d2h", "tm.demux")  # in order
PHASES = (BATCH,) + STEPS + (WAIT,)
LEAVES = STEPS + (WAIT,)
BATCH_OTHER = "batch_other"
LOOP = "loop"
MODULES_LINE = "XLA Modules"  # one event per execution of a program
FLOW_OUT, FLOW_IN = "_p", "_c"  # the profiler's flow ids: enqueue -> run
OFFSET_WINDOW_S = 1.0  # past a trace's first second, the offset holds

Span = Tuple[float, float]  # start_ns, end_ns


def for_run(run) -> Optional[dict]:
    """``reduce_spans`` of the trace a ``--trace 1`` run left in the
    harness's trace directory, kept on the run so that every metric reads
    one reduction; None for an untraced run or a program without the
    spans."""
    if run.trace is None:
        return None
    if "span_reduction" not in vars(run):
        from bench import harness

        run.span_reduction = reduce_spans(find_trace(harness.TRACE_DIR),
                                          harness.WINDOW)
    return run.span_reduction


def ms_per_batch(run, phase: str) -> Optional[float]:
    """Milliseconds of ``phase`` per ``tm.batch`` span in the window."""
    s = for_run(run)
    if not s or not s["batches"]:
        return None
    return 1e3 * s["phases"][phase]["total_s"] / s["batches"]


def _subtract(a: List[Span], b: List[Span]) -> List[Span]:
    """``a`` less ``b``; both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if e > s:
            out.append((s, e))
    return out


def _overlap_ns(a: List[Span], b: List[Span]) -> float:
    """Length of ``a`` and ``b`` together; both sorted and disjoint."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _stats(values_ns: List[float]) -> dict:
    d = np.asarray(values_ns, float) / 1e6
    if not d.size:
        return {"count": 0, "total_s": 0.0}
    return {"count": int(d.size), "total_s": float(d.sum()) / 1e3,
            "p50_ms": float(np.percentile(d, 50)),
            "p99_ms": float(np.percentile(d, 99)),
            "min_ms": float(d.min()), "max_ms": float(d.max())}


def _host(planes, window):
    """-> per host line ``{phase: sorted spans}`` (lines with a phase),
    the window's spans, and ``{flow id: start}`` of the flows host
    events start."""
    lines, windows, flows = [], [], {}
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            by_name = defaultdict(list)
            for ev in ln.events:
                if ev.name in PHASES:
                    by_name[ev.name].append((ev.start_ns, ev.end_ns))
                elif ev.name == window:
                    windows.append((ev.start_ns, ev.end_ns))
                else:
                    for key, flow in ev.stats:
                        if key == FLOW_OUT:
                            flows[flow] = min(ev.start_ns,
                                              flows.get(flow, np.inf))
            if by_name:
                lines.append({k: sorted(v) for k, v in by_name.items()})
    return lines, windows, flows


def _runs(device, flows, lo, hi) -> List[Tuple[float, float, float]]:
    """(start, end, host enqueue start) of each execution on ``device``
    that the host is seen to enqueue inside [lo, hi], by enqueue."""
    runs = []
    for ln in device.lines:
        if ln.name != MODULES_LINE:
            continue
        for ev in ln.events:
            q = flows.get(dict(ev.stats).get(FLOW_IN))
            if q is not None and lo <= q <= hi:
                runs.append((ev.start_ns, ev.end_ns, q))
    return sorted(runs, key=lambda r: r[2])


def _offsets(runs) -> List[float]:
    """Per run (sorted by enqueue), the device clock's offset near it: the
    largest ``enqueue - start`` over the runs enqueued within
    ``OFFSET_WINDOW_S`` of it, as no run starts before its enqueue."""
    q = [r[2] for r in runs]
    bound = [r[2] - r[0] for r in runs]
    width = OFFSET_WINDOW_S * 1e9
    out, best, hi = [], deque(), 0  # best: indices, bounds decreasing
    for k in range(len(runs)):
        while hi < len(runs) and q[hi] <= q[k] + width:
            while best and bound[best[-1]] <= bound[hi]:
                best.pop()
            best.append(hi)
            hi += 1
        while q[best[0]] < q[k] - width:
            best.popleft()
        out.append(bound[best[0]])
    return out


def reduce_spans(path, window: str) -> Optional[dict]:
    """-> ``window_s``, ``busy_s``, ``idle_s``, ``devices``, ``batches``
    (the ``tm.batch`` spans wholly inside the window), ``phases`` (name ->
    count, total_s, p50/p99/min/max ms), ``clock_offset`` (the median ms,
    and its drift from the first run to the last), ``launch_lag`` and
    ``readback_lag`` (the same figures over batches with device work) and
    ``idle_split_s`` (part -> seconds).  None where the trace holds no
    ``tm.batch`` span: a program without them."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(path)).planes)
    lines, windows, flows = _host(planes, window)
    if not windows:
        raise ValueError(f"no {window!r} span in {path}")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    if not any(ln.get(BATCH) for ln in lines):
        return None
    inside = lambda s, e: s >= lo and e <= hi  # noqa: E731

    durations = {name: [] for name in PHASES}
    children = []  # per line: batch index -> {step: span}
    for ln in lines:
        batches = ln.get(BATCH, [])
        starts = [s for s, _ in batches]
        kids: Dict[int, dict] = defaultdict(dict)
        for name in PHASES:
            for s, e in ln.get(name, []):
                counted = (s, e)
                if name in STEPS:
                    i = bisect.bisect_right(starts, s) - 1
                    if i >= 0 and e <= batches[i][1]:
                        kids[i][name] = (s, e)
                        counted = batches[i]
                if inside(*counted):
                    durations[name].append(e - s)
        children.append(kids)
    k = max(range(len(lines)), key=lambda i: len(lines[i].get(BATCH, [])))
    sched, kids = lines[k], children[k]

    devices = [p for p in planes
               if any(ln.name == OPS_LINE for ln in p.lines)]
    runs = [_runs(p, flows, lo, hi) for p in devices]
    out = {
        "window_s": (hi - lo) / 1e9,
        "busy_s": None,
        "idle_s": None,
        "devices": len(devices),
        "batches": len(durations[BATCH]),
        "phases": {name: _stats(durations[name]) for name in PHASES},
        "clock_offset": None,
        "launch_lag": None,
        "readback_lag": None,
        "idle_split_s": None,
    }
    if not devices or not all(runs):
        return out
    offsets = [_offsets(r) for r in runs]  # each device has its own clock
    every = [o for per_device in offsets for o in per_device]
    out["clock_offset"] = {
        "ms": float(np.median(every)) / 1e6,
        "drift_ms": max((o[-1] - o[0] for o in offsets), key=abs) / 1e6}

    # per batch, on the scheduler thread: the runs enqueued inside it (the
    # runtime enqueues on a thread of its own, after tm.launch began)
    batches = sched.get(BATCH, [])
    batch_starts = [s for s, _ in batches]
    work: Dict[int, list] = {}
    for (s, e, q), d in zip((r for rs in runs for r in rs), every):
        i = bisect.bisect_right(batch_starts, q) - 1
        if i >= 0 and q <= batches[i][1]:
            w = work.setdefault(i, [np.inf, -np.inf])
            w[0], w[1] = min(w[0], s + d), max(w[1], e + d)
    launch_lag, readback_lag = [], []
    for i, (s, e) in sorted(work.items()):
        own = kids.get(i, {})
        if not inside(*batches[i]) or "tm.launch" not in own:
            continue
        launch_lag.append(s - own["tm.launch"][0])
        if "tm.d2h" in own:
            readback_lag.append(own["tm.d2h"][1] - e)
    out["launch_lag"] = _stats(launch_lag) if launch_lag else None
    out["readback_lag"] = _stats(readback_lag) if readback_lag else None

    # the idle split, per device, averaged
    in_batch = _union(_clip(batches, lo, hi))
    steps = _union(_clip(
        [sp for name in STEPS for sp in sched.get(name, [])], lo, hi))
    leaves = {name: _union(_clip(sched.get(name, []), lo, hi))
              for name in LEAVES}
    outside = _subtract([(lo, hi)], _union(in_batch + leaves[WAIT]))
    batch_other = _subtract(in_batch, steps)
    split = dict.fromkeys(LEAVES + (BATCH_OTHER, LOOP), 0.0)
    busy = 0.0
    for p, rs, offs in zip(devices, runs, offsets):
        by_start = sorted(zip((r[0] for r in rs), offs))
        starts = [t for t, _ in by_start]
        ops = []
        for ln in p.lines:
            if ln.name == OPS_LINE:
                for ev in ln.events:  # the offset of the run begun last
                    j = max(bisect.bisect_right(starts, ev.start_ns) - 1, 0)
                    d = by_start[j][1]
                    ops.append((ev.start_ns + d, ev.end_ns + d))
        merged = _union(_clip(ops, lo, hi))
        busy += sum(e - s for s, e in merged)
        idle = _subtract([(lo, hi)], merged)
        for name in LEAVES:
            split[name] += _overlap_ns(idle, leaves[name])
        split[BATCH_OTHER] += _overlap_ns(idle, batch_other)
        split[LOOP] += _overlap_ns(idle, outside)
    n = len(devices)
    out["busy_s"] = busy / n / 1e9
    out["idle_s"] = out["window_s"] - out["busy_s"]
    out["idle_split_s"] = {k: v / n / 1e9 for k, v in split.items()}
    return out


def main(argv) -> int:
    from bench.harness import TRACE_DIR, WINDOW

    target = Path(argv[0]) if argv else TRACE_DIR
    path = target if target.is_file() else find_trace(target)
    print(json.dumps(reduce_spans(path, WINDOW), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
