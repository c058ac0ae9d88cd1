"""One run of one benchmark cell: set up, measure, check, report.

Everything a cell needs is found by name: its configuration in
``bench/configs/<config>.json``, with the module that builds its model
(``bench/configs/<model>.py``, ``models`` by default) and its plain
reference, its traffic mix in
``bench/traffic/<traffic>.json`` with the generator module
``bench/traffic/<kind>.py``, and one reader ``bench/metrics/<metric>.py``
per metric that ``BENCHMARK.json`` lists for the cell.  From the program
the harness takes only the system under test: the ``Accelerator`` facade
with its scheduler, engine and counters.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"  # fixed: the path is part of the cache key
TRACE_DIR = ROOT / ".bench_out" / "trace"
WINDOW = "bench.window"  # the host span around the measured window
SLOT = "cell"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_EVENT = "/jax/compilation_cache/cache_"  # + hits, misses


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- what BENCHMARK.json names -------------------------------------------


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def workload(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in bench['workloads']]}")


def metrics_for(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``trace`` its per-layer metrics."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(metric: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_generator(kind: str):
    """The traffic generator module ``bench/traffic/<kind>.py``."""
    return importlib.import_module(f"bench.traffic.{kind}")


def load_reference(name: str):
    """The plain reference module ``bench/configs/<name>.py``."""
    return importlib.import_module(f"bench.configs.{name}")


def load_model(config: dict):
    """The model module ``bench/configs/<config["model"]>.py``
    (``models`` where the configuration names none); an unknown name is
    an error that lists the modules there."""
    name = config.get("model", "models")
    try:
        return importlib.import_module(f"bench.configs.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"bench.configs.{name}":
            raise
        known = sorted(p.stem for p in (BENCH / "configs").glob("*.py")
                       if not p.stem.startswith("_"))
        raise KeyError(f"no model module {name!r} in bench/configs; "
                       f"known: {known}") from None


# -- the device ----------------------------------------------------------


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; raises ``NoChip`` off a TPU."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (devices: {info})")
    if info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{info['count']}")
    return info


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Watch:
    """What the process does besides serving: programs lowered in the
    window (there should be none: every shape is warmed up in set-up),
    persistent-cache hits and misses in set-up (after a cell's first run
    in a checkout, every program should come from the cache), and full
    garbage collections in the window, each a pause of the whole process
    (``gc_full_in_window``: their count and seconds)."""

    def __init__(self):
        self.in_window = False
        self.lowered_in_window = 0
        self.cache = {"hits": 0, "misses": 0}
        self.gc_full = {"count": 0, "seconds": 0.0}
        self._gc_start = None

    def _duration(self, event: str, duration_s: float, **kwargs) -> None:
        if self.in_window and event == LOWERING_EVENT:
            self.lowered_in_window += 1

    def _event(self, event: str, **kwargs) -> None:
        for kind in self.cache:
            if not self.in_window and event == CACHE_EVENT + kind:
                self.cache[kind] += 1

    def _gc(self, phase: str, info: dict) -> None:
        if not self.in_window or info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.gc_full["count"] += 1
            self.gc_full["seconds"] += time.perf_counter() - self._gc_start
            self._gc_start = None

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        gc.callbacks.remove(self._gc)


# -- one run -------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What a metric reader reads: the set-up time, the window's records
    and counters, and with ``--trace 1`` the reduced trace."""

    config: dict
    model: object  # bench.configs.models.BenchModel
    setup_s: float
    window_s: float
    records: object  # bench.traffic.common.Records
    counters: Dict[str, float]  # ServeMetrics deltas over the window
    peak: Optional[dict]  # the chip's peaks (bench/peaks.json)
    trace: Optional[dict] = None  # bench.trace_reduce.reduce_trace


def _counters(metrics) -> Dict[str, float]:
    return {"batches": metrics.batches, "rows": metrics.rows,
            "padded_rows": metrics.padded_rows,
            "engine_s": float(sum(metrics.engine_s))}


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def check(records, pools, truth, reference) -> Dict[str, dict]:
    """Every answer that came back against the plain reference of the
    model's ``truth``: each row's class sums and prediction.

    -> ``{name: {"value": v, "max": limit}}`` or ``{"min": limit}``."""
    rows_wrong = rows_checked = 0
    for k, pool in enumerate(pools):
        n, rows, f = pool.shape
        sel = np.flatnonzero((records.pool == k) & ~records.failed)
        if not sel.size:
            continue
        want = reference.class_sums(truth, pool.reshape(n * rows, f))
        want = want.reshape(n, rows, -1)[records.index[sel]]
        got = np.stack([records.sums[i] for i in sel])
        got_pred = np.stack([records.preds[i] for i in sel])
        want_pred = reference.predictions(want.reshape(-1, want.shape[-1]))
        bad = (got != want).any(axis=-1) | (
            got_pred != want_pred.reshape(got_pred.shape))
        rows_wrong += int(bad.sum())
        rows_checked += int(bad.size)
    return {
        "rows_wrong": {"value": rows_wrong, "max": 0},
        "requests_lost": {"value": int(records.failed.sum()), "max": 0},
        "rows_checked": {"value": rows_checked, "min": 1},
    }


def passed(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c.get("max", np.inf)
               and c["value"] >= c.get("min", -np.inf)
               for c in checks.values())


def run_cell(
    cell: dict,
    config: dict,
    traffic: dict,
    metrics: List[dict],
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_process: float,
    require_tpu: bool = True,
    patch: Optional[Callable] = None,
):
    """One run -> (the result line as a dict, the ``Run`` it was read
    from).

    ``patch(engine, truth)`` may replace the engine's ``class_sums``
    before any traffic: the control and the fault tests put their own
    answers in the program's place with it."""
    import jax

    from bench.traffic.common import TRAFFIC_STREAM
    from bench.work import peak
    from repro.accel import Accelerator
    from repro.compile_cache import enable_compile_cache

    phases = [("imports", time.perf_counter())]
    device = device_info(cell["chips"]) if require_tpu else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    enable_compile_cache()
    pk = peak(device["kind"]) if require_tpu else None
    phases.append(("backend", time.perf_counter()))

    with Watch() as watch:
        model = load_model(config).build(config, seed)
        gen = load_generator(traffic["kind"]).build(
            traffic, model, np.random.default_rng([seed, TRAFFIC_STREAM]))
        phases.append(("model_and_traffic", time.perf_counter()))

        acc = Accelerator.for_models([model.program_model])
        if patch is not None:
            patch(acc.engine, model.truth)
        acc.load(SLOT, acc.compile(model.program_model).to_bytes())
        acc.start()
        phases.append(("envelope_and_load", time.perf_counter()))
        try:
            gen.run(acc, SLOT, gen.warmup_seconds)
            phases.append(("warmup_with_compile", time.perf_counter()))
            setup_s = phases[-1][1] - t_process
            if trace:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                jax.profiler.start_trace(
                    str(TRACE_DIR), profiler_options=_profiler_options())
            before = _counters(acc.metrics)
            watch.in_window = True
            with jax.profiler.TraceAnnotation(WINDOW):
                records = gen.run(acc, SLOT, seconds, trace=trace)
            watch.in_window = False
            counters = _delta(_counters(acc.metrics), before)
            if trace:
                jax.profiler.stop_trace()
        finally:
            acc.stop()
    device["memory_peak_bytes"] = memory_peak_bytes()
    del acc
    gc.collect()

    reduced = None
    if trace:
        from bench.trace_reduce import find_trace, reduce_trace

        reduced = reduce_trace(find_trace(TRACE_DIR), WINDOW,
                               model.kernels.values())
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    checks = check(records, gen.pools, model.truth,
                   load_reference(config["reference"]))
    run = Run(config=config, model=model, setup_s=setup_s,
              window_s=records.t_end - records.t_start, records=records,
              counters=counters, peak=pk, trace=reduced)
    values = {}
    for m in metrics:
        v = load_reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {
        "correct": passed(checks),
        "attempted": int(records.failed.size),
        "failed": int(records.failed.sum()),
        "metrics": values,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compiles_in_window"] = watch.lowered_in_window
    result["compile_cache_in_setup"] = watch.cache
    result["gc_full_in_window"] = watch.gc_full
    result["setup_phases_s"] = {
        name: t - (phases[i - 1][1] if i else t_process)
        for i, (name, t) in enumerate(phases)}
    result["checks"] = checks
    return result, run


def _profiler_options():
    """Host spans and device events; no Python call tracing, which would
    slow the host path it is meant to observe."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def print_result(result: dict) -> None:
    """The result line last on stdout; each compared number beside its
    limit as the last lines on stderr."""
    for name, c in result["checks"].items():
        limit = (f"<= {c['max']}" if "max" in c else f">= {c['min']}")
        print(f"check {name}: {c['value']} (limit {limit})", file=sys.stderr)
    print(json.dumps(result), flush=True)
