"""One-time tools of the benchmark (the rate sweep that fixes a cell's rate)."""
