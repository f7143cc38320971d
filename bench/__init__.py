"""Chip benchmark of MPKLink serving models on a TPU: ``python3 bench/run.py``."""
