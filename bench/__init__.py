"""Benchmark harness of the streaming dynamic-graph engine (see run.py)."""
