"""Benchmark of the cohort-selection service on TPU (see run.py)."""
