"""The benchmark: one command runs one cell of BENCHMARK.json once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that decides a number lives in this directory: traffic
generation, the reduction from spans, counters and the device trace to
metrics, the table of peaks, the operation count of the verify kernel,
the plain reference and the comparison that decides ``correct``. From
the program it takes only the system under test (cometbft_tpu) and its
spans, counters and kernel names.
"""
