"""The on-chip benchmark of ATLAS's serving path (see ``bench/run.py``)."""
