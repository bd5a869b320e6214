"""Chip benchmark of igneous-tpu campaigns; ``benchmark/run.py`` is the entry."""
