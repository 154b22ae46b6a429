"""End-to-end serving driver: gate-and-route over real-compute engines.

Plans with the paper's LP, partitions servers mixed/solo, replays a
synthesized two-class trace through :class:`repro.serving.cluster.RealCluster`
(actual jitted prefill/decode compute + real KV migration), and prints the
revenue/latency summary.  The model runs at its published config, with
params and KV caches in ``cfg.param_dtype`` and random weights from
``--seed``; ``--reduced`` swaps in the CPU-sized variant.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --servers 4 --requests 16
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.core.planning import solve_bundled_lp
from repro.core.types import Pricing, ServicePrimitives, WorkloadClass
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serving.cluster import RealCluster

__all__ = ["serve", "make_workload", "main"]


def make_workload(max_len: int, *, n_requests: int, rate: float,
                  n_servers: int, vocab_size: int, seed: int = 0):
    """Two classes sized to the slot cache, and a Poisson trace of them.

    Prefill-heavy ``code`` draws prompts in [max_len/4, max_len/2] and
    decodes in [max_len/128, max_len/64]; decode-heavy ``conversation``
    draws prompts in [max_len/16, max_len/4] and decodes in
    [max_len/64, max_len/32].  At ``max_len=2048`` prompts span 128-1024
    tokens and decodes 16-64, and every request fits its slot.  The
    classes carry the range midpoints for the planning LP.

    Returns ``(classes, requests)``; a request is ``(t_arrival, cls,
    prompt_tokens, decode_len)``.
    """
    L = int(max_len)
    if L < 128:
        raise ValueError(f"max_len must be >= 128, got {L}")
    ranges = [((L // 4, L // 2), (L // 128, L // 64)),
              ((L // 16, L // 4), (L // 64, L // 32))]
    classes = [
        WorkloadClass(name, prompt_len=sum(p) / 2, decode_len=sum(d) / 2,
                      arrival_rate=rate / 2 / n_servers, patience=0.1)
        for name, (p, d) in zip(("code", "conversation"), ranges)
    ]
    rng = np.random.default_rng(seed)
    reqs, t = [], 0.0
    for _ in range(n_requests):
        t += rng.exponential(1.0 / rate)
        c = int(rng.integers(len(classes)))
        (p_lo, p_hi), (d_lo, d_hi) = ranges[c]
        P = int(rng.integers(p_lo, p_hi + 1))
        D = int(rng.integers(d_lo, d_hi + 1))
        toks = rng.integers(2, vocab_size, size=P).astype(np.int32)
        reqs.append((t, c, toks, D))
    return classes, reqs


def serve(cfg: ModelConfig, *, servers: int = 4, requests: int = 16,
          batch_cap: int = 16, chunk: int = 256, max_len: int = 2048,
          rate: float = 8.0, seed: int = 0, params=None):
    """Plan, build a :class:`RealCluster` and replay a trace through it.

    ``params`` default to random weights from ``seed`` in
    ``cfg.param_dtype``.  Returns ``(cluster, requests, metrics)``; the
    cluster's ``completed`` list holds each request's output tokens.
    """
    if params is None:
        params = M.init_model(cfg, jax.random.PRNGKey(seed),
                              jnp.dtype(cfg.param_dtype))
    prim = ServicePrimitives(batch_cap=batch_cap, chunk=chunk)
    pricing = Pricing()
    classes, reqs = make_workload(max_len, n_requests=requests, rate=rate,
                                  n_servers=servers,
                                  vocab_size=cfg.vocab_size, seed=seed)
    plan = solve_bundled_lp(classes, prim, pricing)
    print(f"LP plan: x*={np.round(plan.x, 4)} "
          f"mixed={plan.mixed_servers(servers)}/{servers} "
          f"R*={plan.revenue_rate:.3f}/server/s")
    cluster = RealCluster(cfg, params, classes, plan, prim, pricing,
                          n_servers=servers, max_len=max_len, seed=seed)
    metrics = cluster.run(reqs, horizon=reqs[-1][0] + 1000.0)
    return cluster, reqs, metrics


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="serve the CPU-sized variant of --arch")
    ap.add_argument("--servers", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch-cap", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="total arrivals/s across classes")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    _, _, metrics = serve(cfg, servers=args.servers, requests=args.requests,
                          batch_cap=args.batch_cap, chunk=args.chunk,
                          max_len=args.max_len, rate=args.rate,
                          seed=args.seed)
    for k, v in metrics.summary().items():
        print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
