"""The on-chip benchmark of RACE: ``python3 bench/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.  See ``bench/harness.py``."""
