"""On-chip benchmark of the served path; entry point ``bench/run.py``."""
