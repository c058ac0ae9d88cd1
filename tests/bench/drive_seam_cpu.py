"""Drive whole benchmark runs of ``seam_model`` on the CPU at a tiny size,
as ``drive_cpu.py`` does, and print one JSON line per case: a sound run
and the control, each with the result line, the run's counters and rows
completed, and its two work-count readers on a peak and a kernel time
given here (the CPU has neither).

    JAX_PLATFORMS=cpu python tests/bench/drive_seam_cpu.py <cache_dir>
"""

import time

T_PROCESS = time.perf_counter()

import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parents[1]), str(HERE)]

import pytest  # noqa: E402

import drive_cpu  # noqa: E402  (the tiny traffic)
import seam_model  # noqa: E402
import seam_reference  # noqa: E402
from bench import control, harness  # noqa: E402

CONFIG = dict(drive_cpu.CONFIG, name="seam", model="seam_model",
              reference="seam_reference", threshold=7)
PEAK = {"int8_ops_per_s": 1e9, "hbm_bytes_per_s": 1e8}
KERNEL_S = 1e-3
READERS = ("serve_mfu_pct", "popcount_roofline")


def main(cache_dir: str) -> int:
    harness.CACHE_DIR = Path(cache_dir)
    harness.TRACE_DIR = Path(cache_dir) / "trace"
    bench = harness.load_benchmark()
    name = "mnist_upload32_closed"
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, "bench.configs.seam_model", seam_model)
        mp.setitem(sys.modules, "bench.configs.seam_reference",
                   seam_reference)
        for case, patch in (("seam", None),
                            ("seam_control", control.control_patch(CONFIG))):
            result, run = harness.run_cell(
                harness.workload(bench, name), CONFIG, drive_cpu.CLOSED,
                harness.metrics_for(bench, name, False), seed=2**31 + 13,
                seconds=0.4, trace=False, t_process=T_PROCESS,
                require_tpu=False, patch=patch)
            given = dataclasses.replace(
                run, peak=PEAK, trace={"kernel_s": {seam_model.KERNEL: KERNEL_S}})
            r = run.records
            done = ~r.failed & (r.done >= r.t_start) & (r.done <= r.t_end)
            print(json.dumps({
                "case": case, "result": result, "counters": run.counters,
                "window_s": run.window_s,
                "rows_done": int(r.rows[done].sum()),
                "readers": {m: harness.load_reader(m)(given)
                            for m in READERS},
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
