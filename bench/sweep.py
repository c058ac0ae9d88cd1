"""Find the highest request rate an open-loop cell sustains: run its
traffic at each rate given, in one process, and print per rate the
latency percentiles over all requests and the p95 of the first and last
quarter of the schedule (a backlog that grows through the run shows as a
last quarter far above the first).

    python bench/sweep.py --workload mnist_mixed_open \\
        --rates 2000,4000,8000 --seconds 5 --seed 3

The cell's traffic file then takes about four fifths of the knee as its
fixed rate.  The benchmark's own runs never run this.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="requests/s, a,b,c")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.configs.models import load_config
    from bench.traffic.common import load_traffic

    bench = harness.load_benchmark()
    cell = harness.workload(bench, args.workload)
    config, traffic = load_config(cell["config"]), load_traffic(cell["traffic"])
    for rate in (float(r) for r in args.rates.split(",")):
        try:
            result, run = harness.run_cell(
                cell, config, dict(traffic, rate_per_s=rate), [],
                seed=args.seed, seconds=args.seconds, trace=False,
                t_process=T_PROCESS)
        except harness.NoChip as e:
            print(f"sweep: {e}; refusing to run", file=sys.stderr)
            return 2
        r = run.records
        lat = np.where(r.failed, np.inf, r.done - r.due) * 1e3
        q = max(lat.size // 4, 1)
        pct = lambda a, p: float(np.percentile(a, p))  # noqa: E731
        print(json.dumps({
            "rate_per_s": rate, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "rows_per_s": float(r.rows[~r.failed].sum()) / run.window_s,
            "p50_ms": pct(lat, 50), "p95_ms": pct(lat, 95),
            "p99_ms": pct(lat, 99), "p95_first_quarter_ms": pct(lat[:q], 95),
            "p95_last_quarter_ms": pct(lat[-q:], 95),
            "gen_lag_p95_ms": pct(r.lag, 95) * 1e3,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
