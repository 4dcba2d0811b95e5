"""Chip benchmark of the design-space sweep (see bench/run.py)."""
