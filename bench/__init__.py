"""Chip benchmark of the serving stack: cells, traffic, references,
trace reduction and per-layer metric readers (see ``bench/run.py``)."""
