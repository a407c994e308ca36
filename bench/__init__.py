"""On-chip benchmark of the coded-FFT service (see BENCHMARK.json and PERF.md)."""
