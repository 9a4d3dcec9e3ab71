"""Benchmark of the skyline system on a TPU (see `bench/run.py`)."""
