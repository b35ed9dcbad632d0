"""chipbench — the repo's benchmark on the chip (see PERF.md).

The yardstick lives here, where later PRs may add files and never edit
one: traffic generation, the reduction from traces and spans to
metrics, the table of peaks, the operation/byte functions, the plain
reference and the comparison that decides `correct`. From the program
it takes only the system under test and its spans and counters.
"""
