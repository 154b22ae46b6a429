"""Vmapped batched scenario generation (seeds x scenarios) in JAX.

The Python :meth:`Scenario.generate` path is exact but serial; sweeps
want *hundreds* of (scenario, seed) traces.  This module compiles one
fixed-shape sampling kernel and evaluates the whole batch as

    jax.vmap over scenarios ( jax.vmap over seeds ( kernel ) )

Representation: every scenario is lowered to a *binned intensity* on a
``T``-point grid over ``[0, H)`` plus per-class length/patience
parameters (padded to the batch's max class count).  Sampling is
Lewis-Shedler thinning against the scenario's rate bound -- ``R``
candidate arrivals at rate ``rate_bound``, each kept with probability
``rate(t)/rate_bound`` -- which is exact for Poisson and
piecewise-constant intensities whose breakpoints lie on the grid, and a
binned approximation otherwise.  MMPP scenarios sample their regime
path *inside* the kernel (one ``lax.scan`` over grid bins, at most one
regime switch per bin -- accurate once ``dt << min holding time``), so
burstiness is preserved per replication rather than averaged away.

Outputs are padded, :class:`repro.data.traces.TraceTensors`-shaped
arrays ``(S, K, R)``; :func:`batch_cell_tensors` /
:func:`batch_cell_requests` extract one cell for the engines.  The
kernel never truncates silently: ``truncated[s, k] = 1`` iff the
candidate budget ``R`` ran out before the horizon (the default budget
makes this a ~4-sigma event).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from repro.data.traces import Request, TraceTensors, validate_requests

from .arrivals import MMPPArrivals
from .scenarios import Scenario

__all__ = [
    "scenario_grid_params",
    "generate_batch",
    "batch_cell_tensors",
    "batch_cell_requests",
    "ScenarioStream",
]

_LEN_FLOOR_P, _LEN_FLOOR_D = 8, 2  # same floors as Scenario.generate


def scenario_grid_params(scn: Scenario, horizon_max: float, T: int,
                         I_max: int, K_max: int, compression: float = 1.0,
                         rate_scale: float = 1.0) -> dict:
    """Lower one scenario to the kernel's padded parameter arrays."""
    factor = rate_scale / compression
    proc = scn.arrivals if factor == 1.0 else scn.arrivals.scaled(factor)
    dt = horizon_max / T
    mids = (np.arange(T) + 0.5) * dt
    is_mmpp = isinstance(proc, MMPPArrivals)
    if is_mmpp:
        k = proc.n_regimes
        levels = np.zeros(K_max)
        switch = np.ones(K_max)
        levels[:k] = np.asarray(proc.levels, dtype=float)
        switch[:k] = np.asarray(proc.switch, dtype=float)
        rate_grid = np.full(T, proc.mean_rate(horizon_max))  # unused branch
        base = proc.base_rate
    else:
        k = 1
        levels, switch, base = np.zeros(K_max), np.ones(K_max), 0.0
        rate_grid = np.array([proc.rate_at(float(t)) for t in mids])
    shares = np.zeros((T, I_max))
    for b, t in enumerate(mids):
        shares[b, : scn.n_classes] = scn.shares_at(float(t))
    mean_p = np.ones(I_max)
    mean_d = np.ones(I_max)
    cv_p = np.ones(I_max)
    cv_d = np.ones(I_max)
    patience = np.full(I_max, np.inf)
    for i, p in enumerate(scn.profiles):
        mean_p[i], mean_d[i] = p.mean_prompt, p.mean_decode
        cv_p[i], cv_d[i] = p.cv_prompt, p.cv_decode
        patience[i] = p.patience
    return {
        "rate_grid": rate_grid.astype(np.float32),
        "share_log": np.log(np.maximum(shares, 1e-30)).astype(np.float32),
        "is_mmpp": np.float32(1.0 if is_mmpp else 0.0),
        "mmpp_base": np.float32(base),
        "mmpp_levels": levels.astype(np.float32),
        "mmpp_switch": switch.astype(np.float32),
        "mmpp_k": np.int32(k),
        "rate_bound": np.float32(proc.rate_bound()),
        "horizon": np.float32(min(scn.horizon, horizon_max)),
        "mean_p": mean_p.astype(np.float32),
        "mean_d": mean_d.astype(np.float32),
        "cv_p": cv_p.astype(np.float32),
        "cv_d": cv_d.astype(np.float32),
        "patience": patience.astype(np.float32),
    }


def _make_kernel(R: int, T: int, dt: float):
    import jax
    import jax.numpy as jnp

    def kernel(par, key):
        k_reg, k_gap, k_acc, k_cls, k_p, k_d = jax.random.split(key, 6)

        # -- effective intensity grid (MMPP: sample the regime path) ----
        def step(j, u):
            p_switch = 1.0 - jnp.exp(-par["mmpp_switch"][j] * dt)
            j_next = jnp.where(u < p_switch,
                               jnp.where(j + 1 >= par["mmpp_k"], 0, j + 1), j)
            return j_next, par["mmpp_base"] * par["mmpp_levels"][j]

        _, mmpp_grid = jax.lax.scan(
            step, jnp.int32(0), jax.random.uniform(k_reg, (T,)))
        rate_grid = jnp.where(par["is_mmpp"] > 0, mmpp_grid, par["rate_grid"])

        # -- candidate arrivals at the bound, thinned to rate(t) --------
        bound = jnp.maximum(par["rate_bound"], 1e-9)
        gaps = jax.random.exponential(k_gap, (R,)) / bound
        times = jnp.cumsum(gaps)
        bins = jnp.clip((times / dt).astype(jnp.int32), 0, T - 1)
        lam_t = rate_grid[bins]
        u = jax.random.uniform(k_acc, (R,))
        accept = (times < par["horizon"]) & (u * bound < lam_t)
        truncated = times[R - 1] < par["horizon"]

        # -- class labels + lognormal lengths + patience ----------------
        cls = jax.random.categorical(k_cls, par["share_log"][bins], axis=-1)

        def lengths(kk, mean, cv, floor):
            sigma2 = jnp.log1p(cv[cls] * cv[cls])
            mu = jnp.log(mean[cls]) - sigma2 / 2
            z = jax.random.normal(kk, (R,))
            val = jnp.exp(mu + jnp.sqrt(sigma2) * z)
            return jnp.maximum(floor, val.astype(jnp.int32))

        P = lengths(k_p, par["mean_p"], par["cv_p"], _LEN_FLOOR_P)
        D = lengths(k_d, par["mean_d"], par["cv_d"], _LEN_FLOOR_D)
        pat = par["patience"][cls]

        # -- compact accepted rows to the front (stable by time) --------
        t_keyed = jnp.where(accept, times, jnp.inf)
        order = jnp.argsort(t_keyed)  # accepted stay in arrival order
        t_s = t_keyed[order]
        valid = jnp.isfinite(t_s)
        return {
            "t": t_s,
            "cls": jnp.where(valid, cls[order], 0).astype(jnp.int32),
            "P": jnp.where(valid, P[order], 1).astype(jnp.int32),
            "D": jnp.where(valid, D[order], 1).astype(jnp.int32),
            "patience": jnp.where(valid, pat[order], jnp.inf),
            "valid": valid,
            "n_real": valid.sum().astype(jnp.int32),
            "truncated": truncated.astype(jnp.int32),
        }

    return kernel


def generate_batch(scenarios: Sequence[Scenario], seeds: Sequence[int],
                   horizon: Optional[float] = None, T: int = 512,
                   R: Optional[int] = None, compression: float = 1.0,
                   rate_scale: float = 1.0) -> dict:
    """Generate ``len(scenarios) x len(seeds)`` traces as ONE vmapped batch.

    Returns host ``numpy`` arrays shaped ``(S, K, R)`` (``t``/``cls``/
    ``P``/``D``/``patience``/``valid``) plus ``(S, K)`` ``n_real`` and
    ``truncated`` counters and the shared ``R``/``horizon`` under
    ``"meta"``.  All scenarios share one compiled kernel: shorter
    scenarios simply stop accepting at their own horizon.
    """
    import jax
    import jax.numpy as jnp

    if not scenarios or not len(seeds):
        raise ValueError("need at least one scenario and one seed")
    H = float(horizon if horizon is not None
              else max(s.horizon for s in scenarios))
    I_max = max(s.n_classes for s in scenarios)
    K_max = max((s.arrivals.n_regimes
                 for s in scenarios if isinstance(s.arrivals, MMPPArrivals)),
                default=1)
    params = [scenario_grid_params(s, H, T, I_max, K_max,
                                   compression=compression,
                                   rate_scale=rate_scale)
              for s in scenarios]
    if R is None:
        # candidate budget: bound * horizon + 4 sigma + slack
        need = max(float(p["rate_bound"]) * H for p in params)
        R = int(need + 4.0 * np.sqrt(max(need, 1.0)) + 64)
    stacked = {k: jnp.stack([jnp.asarray(p[k]) for p in params])
               for k in params[0]}
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    kernel = _make_kernel(int(R), int(T), H / T)
    fn = jax.jit(jax.vmap(jax.vmap(kernel, in_axes=(None, 0)),
                          in_axes=(0, None)))
    out = {k: np.asarray(v) for k, v in fn(stacked, keys).items()}
    out["meta"] = {
        "R": int(R), "T": int(T), "horizon": H,
        "scenarios": [s.name for s in scenarios],
        "seeds": [int(s) for s in seeds],
    }
    return out


class ScenarioStream:
    """Stream one scenario's trace as fixed-shape on-device chunks.

    :func:`generate_batch` materialises a whole ``(S, K, R)`` candidate
    table up front, so its memory ceiling is the trace length.  This
    stream draws the *same law* -- Lewis-Shedler thinning against the
    scenario's rate bound, per-class lognormal lengths, an MMPP regime
    path on the ``T``-point grid -- but hands out padded
    :class:`TraceTensors` chunks of ``chunk_size`` *candidates* at a
    time, so a streamed replay can consume millions of requests while
    holding one chunk.

    Chunk-size invariance (a metamorphic property the differential
    tests pin down): every candidate draws its randomness from
    ``fold_in(key, candidate_index)`` and the arrival clock accumulates
    strictly left-to-right in float64 on the host, so the concatenation
    of the emitted chunks is bitwise independent of ``chunk_size``.

    ``next_chunk()`` returns ``None`` once the candidate clock passes
    the horizon; the final real chunk may be partially filled (its
    ``valid`` mask says how far).
    """

    def __init__(self, scenario: Scenario, seed: int,
                 chunk_size: int = 4096, horizon: Optional[float] = None,
                 T: int = 512, compression: float = 1.0,
                 rate_scale: float = 1.0):
        import jax
        import jax.numpy as jnp

        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        self.scenario = scenario
        self.chunk_size = int(chunk_size)
        self.horizon = float(horizon if horizon is not None
                             else scenario.horizon)
        I = scenario.n_classes
        K = (scenario.arrivals.n_regimes
             if isinstance(scenario.arrivals, MMPPArrivals) else 1)
        par = scenario_grid_params(scenario, self.horizon, T, I, K,
                                   compression=compression,
                                   rate_scale=rate_scale)
        self._dt = self.horizon / T
        self._T = int(T)
        self._bound = max(float(par["rate_bound"]), 1e-9)
        # one regime path per stream (the kernel's scan, emitted rate
        # first, then the switch draw), sampled once so every chunk
        # thins against the same intensity grid
        if float(par["is_mmpp"]) > 0:
            rng = np.random.default_rng(seed)
            grid = np.empty(T)
            j = 0
            for b in range(T):
                grid[b] = float(par["mmpp_base"] * par["mmpp_levels"][j])
                p_switch = 1.0 - np.exp(-float(par["mmpp_switch"][j])
                                        * self._dt)
                if rng.uniform() < p_switch:
                    j = 0 if j + 1 >= int(par["mmpp_k"]) else j + 1
            self._rate_grid = grid
        else:
            self._rate_grid = par["rate_grid"].astype(np.float64)
        shares = np.exp(par["share_log"].astype(np.float64))  # (T, I)
        shares /= np.maximum(shares.sum(axis=1, keepdims=True), 1e-30)
        self._cdf = np.cumsum(shares, axis=1)
        self._mean_p = par["mean_p"].astype(np.float64)
        self._mean_d = par["mean_d"].astype(np.float64)
        self._cv_p = par["cv_p"].astype(np.float64)
        self._cv_d = par["cv_d"].astype(np.float64)
        self._patience = par["patience"].astype(np.float64)
        self._key = jax.random.PRNGKey(int(seed))
        self._i0 = 0
        self._t = 0.0
        self._done = False
        self.n_emitted = 0

        C = self.chunk_size

        def draws(key, i0):
            def one(i):
                k = jax.random.fold_in(key, i)
                kg, ka, kc, kp, kd = jax.random.split(k, 5)
                return (jax.random.exponential(kg),
                        jax.random.uniform(ka),
                        jax.random.uniform(kc),
                        jax.random.normal(kp),
                        jax.random.normal(kd))

            return jax.vmap(one)(jnp.arange(C, dtype=jnp.uint32) + i0)

        self._draw = jax.jit(draws)

    @property
    def exhausted(self) -> bool:
        return self._done

    def next_chunk(self) -> Optional[TraceTensors]:
        if self._done:
            return None
        import jax.numpy as jnp

        C = self.chunk_size
        g, ua, uc, zp, zd = (np.asarray(x, dtype=np.float64)
                             for x in self._draw(self._key,
                                                 jnp.uint32(self._i0)))
        self._i0 += C
        # strict left-to-right accumulation including the carried clock:
        # the association (and hence every bit) matches any chunking
        times = np.add.accumulate(np.concatenate(([self._t], g / self._bound)))[1:]
        self._t = float(times[-1])
        bins = np.clip((times / self._dt).astype(np.int64), 0, self._T - 1)
        accept = ((times < self.horizon)
                  & (ua * self._bound < self._rate_grid[bins]))
        cls = np.minimum((uc[:, None] >= self._cdf[bins]).sum(axis=1),
                         self._cdf.shape[1] - 1)

        def lengths(z, mean, cv, floor):
            sigma2 = np.log1p(cv[cls] * cv[cls])
            mu = np.log(mean[cls]) - sigma2 / 2
            val = np.exp(mu + np.sqrt(sigma2) * z)
            return np.maximum(floor, val.astype(np.int64)).astype(np.int32)

        P = lengths(zp, self._mean_p, self._cv_p, _LEN_FLOOR_P)
        D = lengths(zd, self._mean_d, self._cv_d, _LEN_FLOOR_D)
        n = int(accept.sum())
        t = np.full(C, np.inf)
        cl = np.zeros(C, np.int32)
        Pp = np.ones(C, np.int32)
        Dd = np.ones(C, np.int32)
        pat = np.full(C, np.inf)
        valid = np.zeros(C, bool)
        t[:n] = times[accept]
        cl[:n] = cls[accept]
        Pp[:n] = P[accept]
        Dd[:n] = D[accept]
        pat[:n] = self._patience[cls[accept]]
        valid[:n] = True
        self.n_emitted += n
        if self._t >= self.horizon:
            self._done = True
        return TraceTensors(rid=np.arange(C, dtype=np.int32), t=t,
                            cls=cl, P=Pp, D=Dd, patience=pat,
                            valid=valid, n_real=n)


def batch_cell_tensors(batch: dict, s: int, k: int) -> TraceTensors:
    """One (scenario, seed) cell as engine-ready :class:`TraceTensors`."""
    valid = batch["valid"][s, k]
    R = valid.shape[0]
    t = batch["t"][s, k].astype(np.float64)
    t[~valid] = np.inf
    return TraceTensors(
        rid=np.arange(R, dtype=np.int32),
        t=t,
        cls=batch["cls"][s, k].astype(np.int32),
        P=batch["P"][s, k].astype(np.int32),
        D=batch["D"][s, k].astype(np.int32),
        patience=batch["patience"][s, k].astype(np.float64),
        valid=valid.astype(bool),
        n_real=int(batch["n_real"][s, k]),
        n_dropped=0,
    )


def batch_cell_requests(batch: dict, s: int, k: int) -> list:
    """One (scenario, seed) cell as a validated ``list[Request]``."""
    tt = batch_cell_tensors(batch, s, k)
    reqs = [Request(int(tt.rid[i]), float(tt.t[i]), int(tt.cls[i]),
                    int(tt.P[i]), int(tt.D[i]), float(tt.patience[i]))
            for i in range(tt.R) if tt.valid[i]]
    name = batch["meta"]["scenarios"][s]
    return list(validate_requests(reqs, source=f"generate_batch:{name}"))
