"""Chip benchmark of the KVNAND serving path (see `bench/run.py`)."""
