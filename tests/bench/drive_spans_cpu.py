"""Drive traced benchmark runs on the CPU at a tiny size, as
``drive_cpu.py`` does, and print one JSON line per case: the result line
and the span reduction (``bench/span_reduce.py``) of the trace it left.

    JAX_PLATFORMS=cpu python tests/bench/drive_spans_cpu.py <cache_dir>
"""

import time

T_PROCESS = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parents[1]), str(HERE)]

import drive_cpu  # noqa: E402  (the tiny configuration and traffic)
from bench import harness, span_reduce  # noqa: E402
from bench.trace_reduce import find_trace  # noqa: E402

CASES = {"closed_traced": ("mnist_upload32_closed", drive_cpu.CLOSED),
         "open_traced": ("mnist_mixed_open", drive_cpu.OPEN)}


def main(cache_dir: str) -> int:
    harness.CACHE_DIR = Path(cache_dir)
    harness.TRACE_DIR = Path(cache_dir) / "trace"
    bench = harness.load_benchmark()
    for case, (name, traffic) in CASES.items():
        result, _ = harness.run_cell(
            harness.workload(bench, name), drive_cpu.CONFIG, traffic,
            harness.metrics_for(bench, name, True), seed=2**31 + 12,
            seconds=0.4, trace=True, t_process=T_PROCESS,
            require_tpu=False)
        spans = span_reduce.reduce_spans(find_trace(harness.TRACE_DIR),
                                         harness.WINDOW)
        print(json.dumps({"case": case, "result": result, "spans": spans}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
