"""Chip benchmark: one command runs one cell of ``BENCHMARK.json`` on a TPU."""
