"""Seconds from the start of the process to the start of the window:
loading, making the weights, compiling or reading the compile cache, and
the warm-up in virtual time (host clock)."""


def read(rec):
    return rec["setup_s"]
