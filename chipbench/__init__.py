"""Chip benchmark of the CFU fast path (see BENCHMARK.json and PERF.md)."""
