"""One reader per metric, ``bench/metrics/<metric>.py``, found by the
metric's name in ``BENCHMARK.json``.  ``read(run)`` takes a
``bench.harness.Run`` and returns a number, or None where the run holds
nothing to read (the metric is then left out of the result line)."""
