"""Open-loop traffic: Poisson arrivals, lognormal prompt and decode lengths.

The marginals are those of the program's trace sampler
(``repro.data.traces.sample_lengths``: a lognormal of the class's mean and
coefficient of variation, floored at ``min_prompt``/``min_decode`` tokens),
with a Poisson clock at ``rate_per_s`` in the fleet's virtual time.  Both are
copied here so that no change to the program can move the traffic.  A
request longer than ``max_total_len`` keeps its decode length and gives up
prompt tokens; ``clipped`` counts those taken so far.

The stream of arrivals, classes and lengths is drawn once from the mix's
``pool_seed``, so every run serves the same work at the same virtual times.
The run's seed draws only the prompt token ids.
"""

from __future__ import annotations

import numpy as np

__all__ = ["Traffic"]


def _lognormal(rng, mean: float, cv: float) -> float:
    sigma2 = np.log(1.0 + cv * cv)
    return rng.lognormal(np.log(mean) - sigma2 / 2.0, np.sqrt(sigma2))


class Traffic:
    """The mix's request stream, cut into slices of virtual time.

    ``take(t1)`` returns the requests arriving in ``[t0, t1)``, where ``t0``
    is where the previous call ended, as ``(t - t0, cls, prompt, D)``.
    """

    def __init__(self, mix: dict, seed: int, vocab_size: int):
        self.mix = mix
        self.pool = np.random.default_rng(int(mix["pool_seed"]))
        self.ids = np.random.default_rng([int(seed), 0x7AFF1C])
        self.vocab_size = int(vocab_size)
        self.shares = np.array([c["share"] for c in mix["classes"]], float)
        self.t0 = 0.0
        self.clipped = 0
        self._next = self._draw(0.0)

    def _draw(self, t: float) -> tuple:
        mix, rng = self.mix, self.pool
        t += rng.exponential(1.0 / float(mix["rate_per_s"]))
        cls = int(rng.choice(len(self.shares), p=self.shares))
        c = mix["classes"][cls]
        cap = int(mix["max_total_len"])
        P = max(int(mix["min_prompt"]),
                int(_lognormal(rng, c["mean_prompt"], c["cv_prompt"])))
        D = max(int(mix["min_decode"]),
                int(_lognormal(rng, c["mean_decode"], c["cv_decode"])))
        D = min(D, cap - int(mix["min_prompt"]))
        return t, cls, min(P, cap - D), D, P + D > cap

    def take(self, t1: float) -> list:
        out = []
        while self._next[0] < t1:
            t, cls, P, D, clipped = self._next
            self.clipped += clipped
            toks = self.ids.integers(0, self.vocab_size, size=P,
                                     dtype=np.int32)
            out.append((t - self.t0, cls, toks, D))
            self._next = self._draw(t)
        self.t0 = t1
        return out
