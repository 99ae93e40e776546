"""Chip benchmark of the lakehouse: see ``bench/run.py`` and ``PERF.md``."""
