"""From a profiler trace to the numbers the benchmark reports.

``reduce_trace`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
returns, for the window that the host span ``bench.window`` marks:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), averaged
  over the devices used; ``window_s``: the window's length;
* ``programs``: per jitted program, by its jit name without the ``jit_``
  prefix (line ``XLA Modules``), the device seconds of its executions and
  their count;
* ``breakdown``: the ten operations that took the most device time on
  device 0, each named ``<program>:<op>``, and the
  idle time between operations on device 0, summed by what the host was
  doing: the innermost ``bench.*`` host span over each gap's midpoint.

Host and device events share the trace's clock, up to an offset between
the host's and the chip's timestamps (some tenths of a millisecond on a
v5e).  Where the caller names a host span and the programs it runs once
each (``sync``), the offset is estimated from them: a program starts after
its span starts and ends before the span ends.  Device times are shifted
by it before they are cut to the window.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

__all__ = ["reduce_trace", "reduce_profile", "union_length", "gaps"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_LOOKBACK = 512  # spans searched back from a gap for the one over it


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def program_name(module_event: str) -> str:
    """``jit_decode_step(12)`` -> ``decode_step``."""
    name = re.sub(r"\(\d+\)$", "", module_event)
    return name[4:] if name.startswith("jit_") else name


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def _device_index(name: str) -> int:
    m = re.search(r"(\d+)$", name)
    return int(m.group(1)) if m else 0


def clock_offset(host: list, mods: list, sync) -> float:
    """Nanoseconds to add to device times so that each program named in
    ``sync[1]`` lies inside its ``sync[0]`` host span; 0 if they do not
    pair up one to one."""
    if sync is None:
        return 0.0
    span, progs = sync
    hs = sorted((s, e) for n, s, e in host if n == span)
    ds = sorted((s, e) for s, e, n in mods if n in progs)
    if not hs or len(hs) != len(ds):
        return 0.0
    lo = max(h[0] - d[0] for h, d in zip(hs, ds))
    hi = min(h[1] - d[1] for h, d in zip(hs, ds))
    return 0.5 * (lo + hi) if lo <= hi else lo


def reduce_profile(pd, *, n_devices: int = 1, sync=None) -> dict:
    """Reduce a ``jax.profiler.ProfileData``; see the module docstring."""
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in _events(line)
                         if ev[0].startswith(SPAN_PREFIX)]
    devices.sort(key=lambda p: _device_index(p.name))
    devices = devices[:n_devices]
    if len(devices) < n_devices:
        raise ValueError(f"trace has {len(devices)} TPU planes, "
                         f"{n_devices} expected")
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {WINDOW_SPAN} spans")
    lo, hi = windows[0]

    busy, programs, op_time = [], {}, defaultdict(float)
    idle, offset = None, 0.0
    for k, plane in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        mods = sorted((s, e, program_name(n))
                      for n, s, e in _events(lines[MODULES_LINE]))
        if k == 0:
            offset = clock_offset(host, mods, sync)
        mods = [(s + offset, e + offset, n) for s, e, n in mods]
        ops = [(max(s + offset, lo), min(e + offset, hi), n)
               for n, s, e in _events(lines[OPS_LINE])]
        ops = [(s, e, n) for s, e, n in ops if e > s]
        busy.append(union_length((s, e) for s, e, _ in ops) * 1e-9)
        if k:
            continue
        idle = gaps(((s, e) for s, e, _ in ops), lo, hi)
        for s, e, name in mods:
            if s >= lo and e <= hi:
                p = programs.setdefault(name, {"device_s": 0.0, "count": 0})
                p["device_s"] += (e - s) * 1e-9
                p["count"] += 1
        starts = [s for s, _, _ in mods]
        for s, e, name in ops:
            j = bisect.bisect_right(starts, s) - 1
            prog = mods[j][2] if j >= 0 and s < mods[j][1] else "-"
            op_time[f"{prog}:{op_name(name)}"] += (e - s) * 1e-9

    # Spans nest, so the covering span that started last is the innermost.
    spans = sorted((s, e, n) for n, s, e in host if n != WINDOW_SPAN)
    starts = [s for s, _, _ in spans]
    by_host = defaultdict(float)
    for s, e in idle:
        mid = 0.5 * (s + e)
        j = bisect.bisect_right(starts, mid) - 1
        owner = "outside bench spans"
        for ss, se, n in reversed(spans[max(0, j - _LOOKBACK):j + 1]):
            if se >= mid:
                owner = n
                break
        by_host[owner] += (e - s) * 1e-9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "clock_offset_s": offset * 1e-9,
        "programs": programs,
        "breakdown": {"device_ops": [[n, v] for n, v in top_ops],
                      "idle_gaps": [[n, v] for n, v in top_idle]},
    }


def reduce_trace(trace_dir, *, n_devices: int = 1, sync=None) -> dict:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_profile(ProfileData.from_file(str(files[-1])),
                          n_devices=n_devices, sync=sync)
