"""Share of its roofline that ``decode_step`` reaches: the least time of
every solo step at its live slot lengths (``bench/costs.py``, the larger of
FLOPs over peak and bytes over HBM bandwidth) over the device time of the
``decode_step`` executions in the trace.  Which bound applied to most of
the steps is noted on the run's log."""

from bench import costs


def read(rec):
    tr, peaks = rec["trace"], rec["peaks"]
    p = None if tr is None else tr["programs"].get("decode_step")
    if not p or not p["device_s"] or peaks is None:
        return None
    s = costs.Shapes(rec["config"])
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for st in rec["window"]["steps"]:
        if st[1]:
            continue
        t, bound = costs.least_seconds(*costs.decode_step(s, st[4], st[5]),
                                       peaks)
        least += t
        bounds[bound] += 1
    rec.setdefault("notes", []).append(f"decode_roofline bound: {bounds}")
    return 100.0 * least / p["device_s"]
