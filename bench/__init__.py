"""The chip benchmark of the BST index: cells, traffic, reference and metrics.

``python bench/run_cell.py --workload <config>.<mix> --seed N --seconds S
--trace 0|1`` runs one cell once; ``BENCHMARK.json`` at the repository root
names the cells and metrics.  Everything here is the yardstick: the system
under test is imported from ``src/`` and nothing else of it is used.
"""
