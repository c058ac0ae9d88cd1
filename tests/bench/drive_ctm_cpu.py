"""Drive whole benchmark runs of the Convolutional TM's model module on
the CPU at a tiny geometry, as ``drive_cpu.py`` does for the plain TM, and
print one JSON line per case: a sound run and the control, each with its
result line.

    JAX_PLATFORMS=cpu python tests/bench/drive_ctm_cpu.py <cache_dir>

``test_bench_ctm.py`` runs it in a subprocess of its own, because a run
turns on JAX's persistent compilation cache for the whole process.
"""

import time

T_PROCESS = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parents[1]), str(HERE)]

import drive_cpu  # noqa: E402  (the tiny traffic)
from bench import control, harness  # noqa: E402
from bench.configs.models import load_config  # noqa: E402

# the cell's configuration at a tiny geometry: 8x8 images, a 3x3 window
CONFIG = dict(load_config("tm_ctm_mnist"), name="tiny_ctm", image_h=8,
              image_w=8, window_h=3, window_w=3, n_positions=36,
              n_features=19, input_width=64, n_classes=3, n_clauses=8,
              n_includes=30)
CELL = "ctm_mnist_upload32_closed"


def main(cache_dir: str) -> int:
    harness.CACHE_DIR = Path(cache_dir)
    harness.TRACE_DIR = Path(cache_dir) / "trace"
    bench = harness.load_benchmark()
    for case, patch in (("ctm", None),
                        ("ctm_control", control.control_patch(CONFIG))):
        result, run = harness.run_cell(
            harness.workload(bench, CELL), CONFIG, drive_cpu.CLOSED,
            harness.metrics_for(bench, CELL, False), seed=2**31 + 17,
            seconds=0.4, trace=False, t_process=T_PROCESS,
            require_tpu=False, patch=patch)
        print(json.dumps({"case": case, "result": result,
                          "counters": run.counters}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
