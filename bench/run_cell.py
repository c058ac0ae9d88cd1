"""Run one benchmark cell on the chip and print its result line.

    python bench/run_cell.py --workload mnist_upload32_closed --seed 7 \\
        --seconds 10 --trace 0

Cells, metrics and bounds are in ``BENCHMARK.json``.  The last line of
standard output is the result (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``; with ``--trace 1`` also ``breakdown``), and the
last lines of standard error give each number compared beside its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits
2 and prints no result.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    from bench import harness
    from bench.configs.models import load_config
    from bench.traffic.common import load_traffic

    bench = harness.load_benchmark()
    cell = harness.workload(bench, args.workload)
    try:
        result, _ = harness.run_cell(
            cell, load_config(cell["config"]), load_traffic(cell["traffic"]),
            harness.metrics_for(bench, cell["name"], bool(args.trace)),
            seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            t_process=T_PROCESS,
        )
    except harness.NoChip as e:
        print(f"run_cell: {e}; refusing to run", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
