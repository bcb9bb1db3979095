"""Chip benchmark of FedGiA's round engine (see BENCHMARK.json, PERF.md).

`python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell once on the chips of the machine it is started on.
"""
