"""Mean number of decoding slots per ``ServerEngine.step`` call, read from
the engine's host-side slots before each call (program counter)."""


def read(rec):
    steps = rec["window"]["steps"]
    if not steps:
        return None
    return sum(s[4] for s in steps) / len(steps)
