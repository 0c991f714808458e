"""Chip benchmark of HERO: cells, traffic, metric readers and the
reference that decides `correct` (see `bench/run.py`)."""
