"""Mean milliseconds per ``serve.engine.extract`` of its
``serve.kv.to_host`` child: the copy of a slot's KV cache to the host
(the program's spans, host clock)."""

from bench import program_spans as ps


def read(rec):
    recs = ps.records() or []
    extracts = ps.named(recs, "serve.engine.extract")
    if not extracts:
        return None
    spent = sum(ps.duration(r) for _, r in ps.named(recs, "serve.kv.to_host"))
    return 1e3 * spent / len(extracts)
