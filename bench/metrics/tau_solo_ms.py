"""Device milliseconds per ``decode_step`` execution (device trace)."""


def read(rec):
    tr = rec["trace"]
    p = None if tr is None else tr["programs"].get("decode_step")
    if not p or not p["count"]:
        return None
    return 1e3 * p["device_s"] / p["count"]
