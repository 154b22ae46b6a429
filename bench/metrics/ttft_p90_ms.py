"""90th percentile of the prefill time of requests whose prefill starts and
ends in the window: the wall milliseconds of their server's steps from the
one that ran the first chunk to the one that returned the first token.
Queue wait is left out: it passes in the fleet's virtual time (host clock)."""

from bench.stats import percentile


def read(rec):
    p = percentile(rec["window"]["ttft_s"], 90)
    return None if p is None else 1e3 * p
