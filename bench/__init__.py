"""The chip benchmark: cells, traffic, metric readers and the yardstick.

``python bench/run_cell.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the chip.
"""
