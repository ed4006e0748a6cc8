"""perfbench — the benchmark of paddle_tpu on the chip (BENCHMARK.json).

One command runs one cell once:

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one cell or one per-layer metric
is a data file found by name (configs/, workloads/, layer_metrics/); a kind of
job is a module under drivers/, a model family a module under families/.  The
yardstick — traffic generation, the trace reduction, the peaks table, the
operation and byte counts, the plain reference and the comparison that decides
`correct` — lives here and imports from the program only the system under
test and its counters.
"""
