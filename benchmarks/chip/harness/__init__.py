"""Chip benchmark harness: one cell, one seed, one run (see run.py)."""
