"""The chip benchmark of the overlay server (see chipbench/run.py)."""
