"""On-chip benchmark of compressed-id ANN serving (see ``bench/run.py``)."""
