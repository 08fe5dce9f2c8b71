"""Chip benchmark of the serving and training paths (see ``run.py``)."""
