"""The on-chip benchmark of the DLRM train step (see bench/run.py)."""
