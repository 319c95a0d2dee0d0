"""Chip benchmark of the split planner: see ``bench/run.py`` and PERF.md."""
