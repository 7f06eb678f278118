"""Chip benchmark of the placement system (see ``run.py``)."""
