"""Share of the window's wall time outside the ``step``, ``extract_slot``
and ``inject_slot`` spans: the scheduler's own host time (host clock)."""


def read(rec):
    w = rec["window"]
    inside = (sum(s[3] - s[2] for s in w["steps"]) + sum(w["extract_s"])
              + sum(w["inject_s"]))
    return 100.0 * (1.0 - inside / w["window_s"])
