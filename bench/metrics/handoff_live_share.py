"""Share of the bytes a KV handoff copies to the host that hold cached
tokens: 100 x the sum of ``live_bytes`` over the sum of ``bytes`` of every
``serve.engine.extract`` (the program's counters)."""

from bench import program_spans as ps


def read(rec):
    args = [r[4] for _, r in ps.named(ps.records() or [],
                                       "serve.engine.extract")]
    total = sum(a.get("bytes", 0) for a in args)
    if not total:
        return None
    return 100.0 * sum(a.get("live_bytes", 0) for a in args) / total
