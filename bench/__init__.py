"""Chip benchmark of the NGHF trainer: ``python bench/run.py --help``."""
