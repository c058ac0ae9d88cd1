"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only <suite>[,<suite>...]]
    PYTHONPATH=src python -m benchmarks.run --list

``--list`` prints the available suite names (for shell completion and CI
matrix generation) and exits 0.  ``--only`` selects suites so a CI job only pays for what it checks
(unknown names fail fast with exit code 2 — a typo must not silently
skip a gate).  Compiles go to the persistent cache
``repro.compile_cache`` places (``JAX_COMPILATION_CACHE_DIR`` or
``<checkout>/.jax_cache``).  Prints ``name,us_per_call,derived`` CSV rows per the
harness contract.  Wall times are CPU-container measurements of the
jitted JAX paths; the eFPGA-model columns (cycles/latency/energy) are
derived from the paper's published pipeline/frequency constants (see
tm_bench_common.py).
"""

from __future__ import annotations

import argparse
import importlib
import sys

# suite name -> module (lazy import: suites pull in jax at import time).
# ALL derives from this table, so adding a suite here is the ONLY step —
# a name in ALL can never silently dispatch to the wrong module.
SUITES = {
    "table1": "table1_resources",
    "table2": "table2_latency",
    "fig6": "fig6_memory",
    "fig9": "fig9_tradeoff",
    "tm_serve": "tm_serve",
    "tm_recal": "tm_recal",
    "tm_kernels": "tm_kernels",
    "tm_fleet": "tm_fleet",
    "tm_prune": "tm_prune",
}
ALL = tuple(SUITES)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", type=str, default=",".join(ALL), metavar="SUITE[,SUITE]",
        help=f"comma-separated subset of {', '.join(ALL)}",
    )
    ap.add_argument(
        "--list", action="store_true",
        help="print the available suite names (one per line) and exit 0",
    )
    args = ap.parse_args()
    if args.list:
        for name in ALL:
            print(name)
        return 0
    wanted = [w.strip() for w in args.only.split(",") if w.strip()]
    unknown = [w for w in wanted if w not in SUITES]
    if unknown:
        print(
            f"unknown benchmark suite(s) {', '.join(unknown)}; "
            f"choose from: {', '.join(ALL)}",
            file=sys.stderr,
        )
        return 2

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    for name in wanted:
        mod = importlib.import_module(f".{SUITES[name]}", __package__)
        for row in mod.run():
            print(",".join(str(x) for x in row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
