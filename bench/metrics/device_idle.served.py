"""Share of the traced window in which no operation ran on the chip:
1 - busy / window, from the device trace."""


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
