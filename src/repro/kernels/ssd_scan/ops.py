"""Jit'd public wrapper for the SSD chunk-scan kernel."""

from __future__ import annotations

from functools import partial

import jax

from .kernel import ssd_scan_pallas

__all__ = ["ssd_scan"]


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x_dt, Bm, Cm, log_a, *, chunk=256, interpret=False):
    S = x_dt.shape[1]
    c = min(chunk, S)
    while S % c != 0:
        c //= 2
    return ssd_scan_pallas(x_dt, Bm, Cm, log_a, chunk=c, interpret=interpret)
