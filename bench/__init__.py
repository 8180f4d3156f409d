"""Chip benchmark of the streaming clustering service (see ``run.py``)."""
