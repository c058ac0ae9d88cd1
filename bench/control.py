"""The control of a cell's ``correct``: the configuration's own plain
reference put in the program's place with one guarantee of the
configuration broken (its ``drop_last_include``: the last include of
every clause dropped, as a truncated instruction stream would drop it),
through the cell's own harness, traffic and comparison.  Its
runs must come out not correct; beside them, sound runs of the program
give the lower readings.

    python bench/control.py --workload mnist_upload32_closed \\
        --seeds 11,12,13 --program-seeds 21,22 --seconds 3

One process runs them all and prints one JSON line per run.  The
benchmark's own runs never run this.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def control_patch(config: dict):
    """A ``run_cell`` patch that answers every batch with the reference of
    ``config`` on the model's truth less the last include of each clause,
    in the engine's place."""
    from bench.harness import load_reference

    ref = load_reference(config["reference"])

    def patch(engine, truth) -> None:
        dropped = ref.drop_last_include(truth)
        cap = engine.plan.batch_capacity
        engine.class_sums = lambda prog, x: ref.class_sums(dropped, x, cap)

    return patch


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="control seeds, a,b,c")
    ap.add_argument("--program-seeds", default="",
                    help="seeds of sound program runs, a,b,c")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--require-tpu", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.configs.models import load_config
    from bench.traffic.common import load_traffic

    bench = harness.load_benchmark()
    cell = harness.workload(bench, args.workload)
    config, traffic = load_config(cell["config"]), load_traffic(cell["traffic"])
    runs = [(int(s), "control") for s in args.seeds.split(",") if s]
    runs += [(int(s), "program") for s in args.program_seeds.split(",") if s]
    for seed, side in runs:
        try:
            result, _ = harness.run_cell(
                cell, config, traffic, [], seed=seed, seconds=args.seconds,
                trace=False, t_process=T_PROCESS,
                require_tpu=bool(args.require_tpu),
                patch=control_patch(config) if side == "control" else None)
        except harness.NoChip as e:
            print(f"control: {e}; refusing to run", file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "side": side,
                          "seed": seed, "correct": result["correct"],
                          "attempted": result["attempted"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
