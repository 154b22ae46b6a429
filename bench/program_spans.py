"""The program's own host spans (``repro.telemetry.spans``), for the
metric readers that read them.

The program records spans while the profiler records, which in a run of
the harness is the traced window alone.  A program without the recorder
reads as nothing recorded.
"""

from __future__ import annotations

__all__ = ["records", "named", "duration", "children"]


def records():
    """The records ``[name, t0, t1, parent, args]``, or None where the
    program has no recorder or recorded nothing."""
    try:
        from repro.telemetry import spans
    except ImportError:
        return None
    return spans.records() or None


def named(recs: list, name: str) -> list:
    """``(index, record)`` of every span called ``name``."""
    return [(i, r) for i, r in enumerate(recs) if r[0] == name]


def duration(rec) -> float:
    return rec[2] - rec[1]


def children(recs: list, parents) -> dict:
    """Index of each parent in ``parents`` -> the summed seconds of the
    spans opened directly inside it."""
    out = dict.fromkeys(parents, 0.0)
    for r in recs:
        if r[3] in out:
            out[r[3]] += duration(r)
    return out
