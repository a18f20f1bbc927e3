"""Chip benchmark of the CEC control plane (see ``run.py``)."""
