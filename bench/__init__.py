"""Chip benchmark of the replicated partitioner and scheduler (see run.py)."""
