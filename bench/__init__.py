"""The on-chip benchmark of PerFedS²: one cell, one run (see ``run.py``)."""
