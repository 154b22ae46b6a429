"""Mean wall milliseconds of a ``ServerEngine.step`` call less the device
time of the program it ran (``decode_step`` or ``mixed_step``, from the
device trace): the engine's host time per step."""

PROGRAMS = ("decode_step", "mixed_step")


def read(rec):
    tr, steps = rec["trace"], rec["window"]["steps"]
    if tr is None or not steps:
        return None
    device = sum(tr["programs"].get(p, {}).get("device_s", 0.0)
                 for p in PROGRAMS)
    wall = sum(s[3] - s[2] for s in steps)
    return 1e3 * (wall - device) / len(steps)
