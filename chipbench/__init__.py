"""Chip benchmark of the served PostSI path (see ``BENCHMARK.json``)."""
