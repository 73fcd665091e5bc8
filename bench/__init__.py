"""Chip benchmark of the serving engine: see BENCHMARK.json and bench/run.py."""
