"""Chip benchmark of the GRPO main path (see ``run.py``)."""
