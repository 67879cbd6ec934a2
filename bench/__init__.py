"""Chip benchmark of the simulator (see ``BENCHMARK.json`` and ``PERF.md``)."""
