"""Chip benchmark of the offloaded serving engine (see bench/run.py)."""
