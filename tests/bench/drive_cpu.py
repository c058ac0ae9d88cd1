"""Drive whole benchmark runs on the CPU at a tiny size, with the chip
check skipped, and print one JSON line per case: sound runs, the control,
and the faults a serving cell can have planted in the timed path.

    JAX_PLATFORMS=cpu python tests/bench/drive_cpu.py <cache_dir>

``test_bench_harness.py`` runs it in a subprocess of its own, because a
run turns on JAX's persistent compilation cache for the whole process.
"""

import time

T_PROCESS = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from bench import control, harness  # noqa: E402

CONFIG = {"name": "tiny", "n_classes": 3, "n_clauses": 8, "n_features": 16,
          "n_includes": 40, "include_rule": "feature",
          "reference": "tm_reference"}
CLOSED = {"kind": "closed", "clients": 4, "rows": 8, "lane": "normal",
          "pool_requests": 32, "satisfy_clauses": 2, "warmup_seconds": 0.2}
OPEN = {"kind": "open_poisson", "rate_per_s": 300.0, "satisfy_clauses": 2,
        "warmup_seconds": 0.2,
        "mix": [{"share": 0.8, "rows": 1, "lane": "critical",
                 "pool_requests": 64},
                {"share": 0.2, "rows": 8, "lane": "normal",
                 "pool_requests": 16}]}


def wrap(fault):
    """A patch that breaks the engine's answers with ``fault(sums,
    state)``, where the engine computed them."""

    def patch(engine, truth):
        real, state = engine.class_sums, {}

        def class_sums(prog, x):
            return fault(np.array(real(prog, x)), state)

        engine.class_sums = class_sums

    return patch


def altered(sums, state):
    sums[0, 0] += 1  # one answer changed where it is produced
    return sums


def half_left_out(sums, state):
    sums[(sums.shape[0] + 1) // 2:] = 0  # the batch's second half unserved
    return sums


def stale(sums, state):
    prev = state.get("prev")  # every batch answered with the one before
    state["prev"] = sums
    return sums if prev is None or prev.shape != sums.shape else prev.copy()


CASES = {
    "closed": (CLOSED, False, None),
    "closed_traced": (CLOSED, True, None),
    "open": (OPEN, False, None),
    "open_traced": (OPEN, True, None),
    "control": (CLOSED, False, control.control_patch(CONFIG)),
    "fault_altered": (CLOSED, False, wrap(altered)),
    "fault_half_left_out": (CLOSED, False, wrap(half_left_out)),
    "fault_stale": (CLOSED, False, wrap(stale)),
}


def main(cache_dir: str) -> int:
    harness.CACHE_DIR = Path(cache_dir)
    harness.TRACE_DIR = Path(cache_dir) / "trace"
    bench = harness.load_benchmark()
    cells = {"closed": "mnist_upload32_closed", "open": "mnist_mixed_open"}
    for case, (traffic, trace, patch) in CASES.items():
        name = cells["open" if traffic is OPEN else "closed"]
        result, _ = harness.run_cell(
            harness.workload(bench, name), CONFIG, traffic,
            harness.metrics_for(bench, name, trace), seed=2**31 + 11,
            seconds=0.4, trace=trace, t_process=T_PROCESS,
            require_tpu=False, patch=patch)
        print(json.dumps({"case": case, "result": result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
