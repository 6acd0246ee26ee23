"""Chip benchmark of the DiLoCo trainer (see bench/run.py)."""
