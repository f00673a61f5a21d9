"""GraphH's chip benchmark: the harness, its data files and its yardstick.

Run a cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; BENCHMARK.json at the repository's root lists
the cells and metrics, and bench/cells.py says where each piece lives.
"""
