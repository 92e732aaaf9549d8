"""Chip benchmark of the TREES job service (``python3 bench/run.py``)."""
