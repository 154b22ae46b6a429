"""Microseconds of gate and router work per scheduler event: the self time
(their child spans left out) of every ``serve.cluster.admit`` and
``serve.cluster.route`` span, over the heap events that the
``serve.cluster.run`` spans handled (the program's spans, host clock)."""

from bench import program_spans as ps


def read(rec):
    recs = ps.records() or []
    events = sum(r[4].get("events", 0)
                 for _, r in ps.named(recs, "serve.cluster.run"))
    if not events:
        return None
    own = dict(ps.named(recs, "serve.cluster.admit")
               + ps.named(recs, "serve.cluster.route"))
    inside = ps.children(recs, own)
    spent = sum(ps.duration(r) - inside[i] for i, r in own.items())
    return 1e6 * spent / events
