"""Chip benchmark of the HaS serving path (see run.py)."""
