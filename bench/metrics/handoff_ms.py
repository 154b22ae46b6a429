"""Mean wall milliseconds of one KV handoff: one ``extract_slot`` plus one
``inject_slot`` call (host clock)."""


def read(rec):
    w = rec["window"]
    if not w["extract_s"] or not w["inject_s"]:
        return None
    return 1e3 * (sum(w["extract_s"]) / len(w["extract_s"])
                  + sum(w["inject_s"]) / len(w["inject_s"]))
