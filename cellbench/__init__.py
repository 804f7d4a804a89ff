"""cellbench — the cell benchmark of tpu-bft (BENCHMARK.json's `paths`).

The yardstick lives here, where a PR that changes the program cannot
change it: traffic generation, the plain references, the comparison that
decides `correct`, the reduction from trace, spans and counters to
metrics, the table of peaks and the operation counts. From the program
it takes the system under test and its counters, spans and kernel names.
"""
