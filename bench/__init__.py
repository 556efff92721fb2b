"""The benchmark of the served Space Saving tier (see ``bench/run.py``)."""
