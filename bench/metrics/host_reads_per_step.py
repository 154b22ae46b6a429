"""Mean device-to-host reads per ``serve.engine.step``: its ``host_reads``
counter, the token fetch, one read per decoding slot and the first token
of a prompt's last chunk (the program's counter)."""

from bench import program_spans as ps


def read(rec):
    steps = ps.named(ps.records() or [], "serve.engine.step")
    if not steps:
        return None
    return sum(r[4].get("host_reads", 0) for _, r in steps) / len(steps)
