"""The benchmark: see benchmark/README.md. Run as
``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout."""
