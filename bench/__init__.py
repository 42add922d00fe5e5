"""On-chip benchmark of the lock system: one cell per run of `run.py`."""
