"""Mean milliseconds per ``serve.engine.step`` spent in its
``serve.engine.account`` child: the per-slot reads and updates after the
program's output reached the host (the program's spans, host clock)."""

from bench import program_spans as ps


def read(rec):
    recs = ps.records() or []
    steps = ps.named(recs, "serve.engine.step")
    if not steps:
        return None
    spent = sum(ps.duration(r)
                for _, r in ps.named(recs, "serve.engine.account"))
    return 1e3 * spent / len(steps)
