"""Chip benchmark of the streaming clusterer (see ``run.py``)."""
