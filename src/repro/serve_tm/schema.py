"""The single source of truth for the ``ServeMetrics.summary()`` schema.

Three places render or validate this schema and used to drift silently:

  * ``ServeMetrics.summary()`` (serve_tm/metrics.py) builds the dict;
  * ``benchmarks/check_regression.py`` validates every per-backend
    summary inside ``BENCH_tm_serve.json`` against it;
  * the docs/accel.md "Serving metrics" table documents it for humans.

The golden-schema test (tests/test_api_and_schema.py) pins all three to
the constants below: ``summary()`` must produce EXACTLY these keys, the
regression gate must require them, and every key must appear in the docs
table.  Change the schema here first; the test tells you what else to
touch.

This module is deliberately import-free pure data: the regression gate
loads it by file path (no package init, no jax) so it stays runnable as
a standalone script.
"""

# priority lanes, in service order (batching.PRIORITIES re-exports this)
LANES = ("critical", "high", "normal", "low")

# top-level summary() keys
SUMMARY_KEYS = (
    "batches",
    "rows",
    "requests_completed",
    "completion_wakes",
    "swaps",
    "fill_ratio",
    "throughput_dps",
    "engine_us",
    "request_latency_us",
    "swap_us",
    "recals",
    "rollbacks",
    "recal_train_s",
    "recal_compress_s",
    "sheds",
    "admission_rejects",
    "deadline_misses",
    "retries",
    "failovers",
    "quarantines",
    "probes",
    "lanes",
)

# keys of each lanes.<lane> sub-dict
LANE_KEYS = (
    "completed",
    "shed",
    "rejected",
    "deadline_miss",
    "queue_delay_us",
    "latency_us",
    "slo_attainment",
)

# percentile sub-dicts: which keys carry {p50, p95, p99} vs {p50, p99}
PCT3_KEYS = ("engine_us", "request_latency_us", "swap_us",
             "recal_train_s", "recal_compress_s")
PCT2_KEYS = ("queue_delay_us", "latency_us")  # inside each lane

# keys of the fleet-level ServeMetrics.aggregate() dict (repro.fleet
# pools render this for BENCH_tm_fleet.json; validated the same way)
AGGREGATE_KEYS = (
    "nodes",
    "batches",
    "rows",
    "requests_completed",
    "swaps",
    "sheds",
    "admission_rejects",
    "deadline_misses",
    "retries",
    "failovers",
    "quarantines",
    "probes",
    "recals",
    "rollbacks",
    "throughput_dps",
    "fill_ratio",
    "lanes",
)

# keys of each aggregate lanes.<lane> sub-dict (counters only: node
# snapshots carry percentiles, which cannot be merged after the fact)
AGGREGATE_LANE_KEYS = (
    "completed",
    "shed",
    "rejected",
    "deadline_miss",
    "slo_attainment",
)

# fleet health: circuit-breaker states and the per-node dict
# fleet.FleetHealth.summary() renders (validated inside the chaos
# scenario of BENCH_tm_fleet.json; pinned by the golden-schema test)
HEALTH_STATES = ("healthy", "degraded", "quarantined", "half_open")

HEALTH_NODE_KEYS = (
    "state",
    "successes",
    "failures",
    "consecutive_failures",
    "error_rate",
    "retries",
    "failovers",
    "overloads",
    "quarantines",
    "probes",
)
