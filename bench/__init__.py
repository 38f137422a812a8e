"""The on-chip benchmark of the FINGER fleet (``python bench/run.py``)."""
