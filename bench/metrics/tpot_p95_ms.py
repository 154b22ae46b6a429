"""95th percentile of the gap a user sees between output tokens: one sample
per output token after a request's first, worth the wall milliseconds of
the ``ServerEngine.step`` call that produced it (host clock)."""

from bench.stats import weighted_percentile


def read(rec):
    p = weighted_percentile(rec["window"]["tpot"], 95)
    return None if p is None else 1e3 * p
