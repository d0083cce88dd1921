"""On-chip benchmark of the MoE trainer: one cell per entry of the root
``BENCHMARK.json``, run as ``python3 chipbench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``."""
