"""The chip benchmark of the served sparse product: cells, traffic, metrics.

``BENCHMARK.json`` at the repository root names every cell; this package
finds each configuration, traffic mix and metric by that name. Run one cell
with ``python3 perfbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` on a machine that holds the chips it asks for.
"""
