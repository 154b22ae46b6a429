"""Uniformized JAX simulation of the aggregate CTMC (jit + vmap batched).

Same stochastic law as :class:`repro.core.simulator.CTMCSimulator` -- the
paper's aggregate many-server CTMC (Section 2.3) under the gate-and-route
policy family -- re-expressed so the event loop becomes a fixed-length
``jax.lax.scan``.  That makes one replication jittable and a whole
replication batch a single ``jax.vmap`` over PRNG keys, which is what lets
the convergence experiments (EC.8.5) scale to thousands of replications at
n up to 10^3.

**Uniformization.**  The exact CTMC jumps at state-dependent total rate
``R(s)``.  Uniformization picks a constant ``Lambda >= sup_s R(s)``, runs a
Poisson(``Lambda``) clock, and at each tick executes a real transition with
probability ``R(s)/Lambda`` (otherwise a self-loop).  The embedded process
has exactly the original law, but every step is structurally identical --
a categorical draw over a fixed-length rate vector -- so it scans.  The
bound used here (see ``docs/SIMULATORS.md`` for the derivation):

    Lambda =   n * sum_i lambda_i              (arrivals)
             + M * max_i mu_p,i                (prefills; X_+ <= M)
             + cap_m * max_i mu_m,i            (mixed decodes; Y_m+ <= cap_m)
             + cap_s * max_i mu_s,i            (solo decodes;  Y_s+ <= cap_s)
             + sum_i theta_i * (Qp_cap_i + Qd_cap_i)   (abandonment caps)

where ``cap_m = (B-1) * M`` (0 for prefill-only mixed servers) and
``cap_s = B * (n - M)`` are the static decode-slot capacities.  The first
four terms are hard pathwise bounds.  Abandonment rates are proportional
to *unbounded* queue lengths, so they are clipped at generous per-class
caps ``Q*_cap_i`` (default ``cap_margin * n lambda_i / theta_i`` plus
fluctuation slack -- several times the no-service-at-all equilibrium, far
outside the stable regime the policies operate in).  Steps on which a
queue actually exceeds its cap under-sample abandonment; they are counted
in ``clip_steps`` so callers can assert the clip never engaged (the
equivalence tests do).

**Self-loop skipping (default stepping mode).**  On the ``Lambda`` clock a
run of self-loops out of state ``s`` is Geometric(``R(s)/Lambda``), and a
geometric number of Exp(``Lambda``) ticks is exactly one Exp(``R(s)``)
holding time -- so the self-loop runs can be collapsed and every scan step
made a *real* transition (the embedded-jump / SSA form of the same chain).
The scan length then comes from a pathwise conservation law instead of the
``Lambda * T`` tick budget: every prefill completion or prefill abandon
consumes one arrival, every decode completion or decode abandon consumes
one prefill completion, so with ``A`` arrivals there are at most ``3 A``
events, and ``A`` itself is Poisson(``n sum_i lambda_i * T``).  The
default ``stepping="events"`` uses this budget (~``3 n lambda T`` steps,
unclipped exact rates, no self-loops); ``stepping="ticks"`` runs the
strict ``Lambda``-clock form (~``Lambda * T`` steps) for when a
fixed-rate clock is wanted, e.g. to couple replications tick-by-tick.
Both modes stop accounting at the horizon; if the step budget is ever
exhausted early (Poisson tail), ``t_end < horizon`` reports it.

**Semantics parity** with the Python engine (same documented deviations):
FCFS buffer pulls are proportional-to-queue-length draws, mixed decodes
always run at ``mu_m``, and at most one prefill admission per event (an
invariant of the gate family when starting from an empty state, which is
why the Python engine's ``while`` admission loop collapses to one
branchless update here).

Supported policy surface (mirrors :class:`CTMCSimulator` exactly):

* gates: :class:`OccupancyGate`, :class:`PriorityRatioGate`,
  :class:`FCFSGate`;
* routers: ``solo_first`` (also used for ``immediate`` / ``local_fcfs``,
  exactly as the aggregate Python engine does) and ``randomized``
  (incl. the EC.7 pool weights);
* charging: ``bundled`` | ``separate``.

Not supported: event-resolution trajectory recording (``record_every``)
and warm starts -- use the Python engine for those.  Time-*binned*
trajectories are available on-device via ``telemetry=`` (a
:class:`repro.telemetry.probes.ProbeSpec`), which threads fixed-shape
``tlm_*`` probe arrays through the scan carry; ``telemetry=None`` (the
default) compiles the byte-identical bare kernel.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry.probes import (ctmc_probe_carry, extract_probes,
                                    resolve_probe_spec,
                                    wrap_ctmc_step_probes)

from .policies import FCFSGate, OccupancyGate, PolicySpec, PriorityRatioGate
from .simulator import CTMCResult
from .types import (Pricing, ServicePrimitives, WorkloadClass, rate_arrays,
                    resolve_primitives)

__all__ = [
    "UniformizedCTMC",
    "uniformization_bound",
    "run_uniformized",
    "run_uniformized_batch",
]

_EPS_TARGET = 1e-12  # OccupancyGate's "class is never admitted" threshold


def _gate_kind(policy: PolicySpec) -> str:
    gate = policy.gate
    if isinstance(gate, OccupancyGate):
        return "occupancy"
    if isinstance(gate, PriorityRatioGate):
        return "priority"
    if isinstance(gate, FCFSGate):
        return "fcfs"
    raise ValueError(
        f"ctmc_jax does not support gate {type(gate).__name__}; "
        "use the Python CTMCSimulator")


def _categorical(u, weights):
    """Index ~ weights/sum(weights) from one uniform draw.

    ``side='right'`` on the cumsum means zero-weight entries are never
    selected; an all-zero vector returns the last index (callers mask
    that case with their own validity flag).
    """
    c = jnp.cumsum(weights)
    return jnp.minimum(jnp.searchsorted(c, u * c[-1], side="right"),
                       weights.shape[0] - 1)


def uniformization_bound(classes: Sequence[WorkloadClass],
                         prim: ServicePrimitives, policy: PolicySpec,
                         n: int, cap_margin: float = 6.0,
                         kv_xfer: float = 0.0) -> dict:
    """Static rate bound + abandonment caps for one instance.

    Returns ``{"Lambda", "M", "cap_m", "cap_s", "qp_cap", "qd_cap"}`` as
    plain numpy values (``qp_cap``/``qd_cap`` are per-class arrays, inf
    where ``theta_i == 0`` -- a zero rate needs no cap).
    """
    prim = resolve_primitives(prim)
    arr = rate_arrays(classes, prim, kv_xfer)
    lam_tot = n * arr["lam"]
    theta = arr["theta"]
    M = policy.mixed_target(n)
    B = prim.batch_cap
    cap_m = 0.0 if policy.prefill_only_mixed else float((B - 1) * M)
    cap_s = float(B * (n - M))
    with np.errstate(divide="ignore", invalid="ignore"):
        base = np.where(theta > 0, lam_tot / np.maximum(theta, 1e-300), 0.0)
    cap = np.ceil(cap_margin * base + 20.0 * np.sqrt(base + 1.0) + 100.0)
    qp_cap = np.where(theta > 0, cap, np.inf)
    qd_cap = np.where(theta > 0, cap, np.inf)
    ab = float(np.sum(np.where(theta > 0, theta * cap, 0.0)))
    lam = (float(lam_tot.sum())
           + float(M * arr["mu_p"].max())
           + cap_m * float(arr["mu_m"].max())
           + cap_s * float(arr["mu_s"].max())
           + 2.0 * ab)
    return {"Lambda": lam, "M": float(M), "cap_m": cap_m, "cap_s": cap_s,
            "qp_cap": qp_cap, "qd_cap": qd_cap}


def _build_step(params: dict, key, gate_kind: str, router_kind: str,
                charging: str, has_pw: bool, stepping: str):
    """Step closure for the scan: one Lambda-clock tick (``"ticks"``) or
    one real transition with self-loops skipped (``"events"``)."""
    I = params["lam_tot"].shape[0]
    lam = params["Lambda"]
    dtype = params["lam_tot"].dtype

    def step(carry, idx):
        u = jax.random.uniform(jax.random.fold_in(key, idx), (4,),
                               dtype=dtype)
        qp, x = carry["qp"], carry["x"]
        qdm, qds = carry["qdm"], carry["qds"]
        ym, ys = carry["ym"], carry["ys"]
        t = carry["t"]
        horizon, warmup = params["horizon"], params["warmup"]
        qd = qdm + qds

        active = t < horizon

        # -- holding time + which event fires ------------------------------
        if stepping == "ticks":
            # Lambda-clock: abandonment rates clipped at the caps so the
            # static bound Lambda >= R(s) holds; excess mass self-loops
            rates = jnp.concatenate([
                params["lam_tot"],
                params["mu_p"] * x,
                params["mu_m"] * ym,
                params["mu_s"] * ys,
                params["theta"] * jnp.minimum(qp, params["qp_cap"]),
                params["theta"] * jnp.minimum(qd, params["qd_cap"]),
            ])
            c = jnp.cumsum(rates)
            dt = -jnp.log1p(-u[0]) / lam
            t_new = jnp.minimum(t + dt, horizon)
            idx_ev = jnp.searchsorted(c, u[1] * lam, side="right")
            live = idx_ev < 6 * I  # ticks past R(s) are self-loops
        else:
            # embedded jumps: exact (unclipped) rates, Exp(R(s)) holding
            rates = jnp.concatenate([
                params["lam_tot"],
                params["mu_p"] * x,
                params["mu_m"] * ym,
                params["mu_s"] * ys,
                params["theta"] * qp,
                params["theta"] * qd,
            ])
            c = jnp.cumsum(rates)
            total = c[-1]
            dt = jnp.where(total > 0, -jnp.log1p(-u[0])
                           / jnp.maximum(total, 1e-30), horizon)
            t_new = jnp.minimum(t + dt, horizon)
            idx_ev = jnp.searchsorted(c, u[1] * total, side="right")
            live = total > 0
        # time-average accumulation over [t, t_new) with the PRE-event
        # state (the event, if any, happens at t_new); events at exactly
        # the horizon are never applied (matching the Python loop's break)
        eff = jnp.clip(t_new - jnp.maximum(t, warmup), 0.0) * active
        ev = active & (t_new < horizon) & live
        idx_c = jnp.minimum(idx_ev, 6 * I - 1)
        cat = idx_c // I
        i = idx_c % I
        one = jax.nn.one_hot(i, I, dtype=dtype)

        is_arr = ev & (cat == 0)
        is_pc = ev & (cat == 1)
        is_md = ev & (cat == 2)
        is_sd = ev & (cat == 3)
        is_ap = ev & (cat == 4)
        is_ad = ev & (cat == 5)

        def f(b):
            return b.astype(dtype)

        rev_on = f(t_new > warmup)
        free_s = params["cap_s"] - ys.sum()
        free_m = params["cap_m"] - ym.sum()

        # -- route the decode of a completed class-i prefill ---------------
        if router_kind == "randomized":
            go_solo = u[2] <= params["p_s"][i]
            route_ys = f(is_pc & go_solo & (free_s >= 1))
            route_qds = f(is_pc & go_solo & (free_s < 1))
            route_ym = f(is_pc & ~go_solo & (free_m >= 1))
            route_qdm = f(is_pc & ~go_solo & (free_m < 1))
        else:  # solo_first (single logical buffer kept in the solo half)
            route_ys = f(is_pc & (free_s >= 1))
            route_ym = f(is_pc & (free_s < 1) & (free_m >= 1))
            route_qds = f(is_pc & (free_s < 1) & (free_m < 1))
            route_qdm = jnp.zeros((), dtype)

        # -- pull from the buffer into the slot a decode completion freed --
        pull = is_md | is_sd
        if router_kind == "randomized":
            qpool = jnp.where(is_sd, qds, qdm)
            mask = f(qpool >= 1)
            if has_pw:
                wsel = jnp.where(is_sd, params["pw_s"], params["pw_m"])
                wsel = wsel * mask
                probs = jnp.where(wsel.sum() > 0, wsel, qpool * mask)
            else:
                probs = qpool * mask
            j = _categorical(u[2], probs)
            pull_ok = pull & (mask.sum() >= 1)
            pull_from_ds = f(pull_ok & is_sd)
            pull_from_dm = f(pull_ok & is_md)
        else:
            qtot = qds + qdm
            j = _categorical(u[2], qtot)
            pull_ok = pull & (qtot.sum() >= 1)
            take_ds = qds[j] >= 1
            pull_from_ds = f(pull_ok & take_ds)
            pull_from_dm = f(pull_ok & ~take_ds)
        onej = jax.nn.one_hot(j, I, dtype=dtype)
        pull_to_ys = f(pull_ok & is_sd)
        pull_to_ym = f(pull_ok & is_md)

        # -- decode abandonment: which buffer half loses the job -----------
        denom = jnp.maximum(qds[i] + qdm[i], 1.0)
        ab_take_s = (qds[i] >= 1) & ((qdm[i] < 1) | (u[2] < qds[i] / denom))
        ab_ds = f(is_ad & ab_take_s)
        ab_dm = f(is_ad & ~ab_take_s)

        # -- stage 1: apply the event --------------------------------------
        qp1 = qp + one * (f(is_arr) - f(is_ap))
        x1 = x - one * f(is_pc)
        ym1 = ym + one * (route_ym - f(is_md)) + onej * pull_to_ym
        ys1 = ys + one * (route_ys - f(is_sd)) + onej * pull_to_ys
        qdm1 = qdm + one * (route_qdm - ab_dm) - onej * pull_from_dm
        qds1 = qds + one * (route_qds - ab_ds) - onej * pull_from_ds

        # -- stage 2: prefill admission (at most one needed per event) -----
        adm_ev = is_arr | is_pc
        free_p = params["M"] - x1.sum()
        if gate_kind == "occupancy":
            mask = (qp1 >= 1) & (params["x_star"] > _EPS_TARGET)
            xi = ((x1 + 1.0 - params["n"] * params["x_star"])
                  / jnp.maximum(params["x_star"], 1e-30))
            keyv = jnp.where(mask, xi, jnp.inf)
            tie = mask & (keyv == keyv.min())
            delta = qp1 - params["n"] * params["qp_star"]
            cand = jnp.argmax(jnp.where(tie, delta, -jnp.inf))
            can_admit = mask.any()
        elif gate_kind == "priority":
            mask = qp1 >= 1
            cand = jnp.argmax(jnp.where(mask, params["ratio"], -jnp.inf))
            can_admit = mask.any()
        else:  # fcfs: head-of-line class ~ queue lengths (exchangeable)
            cand = _categorical(u[3], qp1)
            can_admit = qp1.sum() >= 1
        admit = f(adm_ev & can_admit & (free_p >= 1))
        onec = jax.nn.one_hot(cand, I, dtype=dtype)
        qp2 = qp1 - onec * admit
        x2 = x1 + onec * admit

        # -- revenue -------------------------------------------------------
        if charging == "separate":
            rev_inc = (params["w_pre"][i] * f(is_pc)
                       + params["w_dec"][i] * (f(is_md) + f(is_sd)))
        else:
            rev_inc = params["w"][i] * (f(is_md) + f(is_sd))
        rev_inc = rev_inc * rev_on

        if stepping == "ticks":
            clipped = active & (
                jnp.any((params["theta"] > 0) & (qp > params["qp_cap"]))
                | jnp.any((params["theta"] > 0) & (qd > params["qd_cap"])))
        else:  # exact rates; nothing to clip
            clipped = jnp.zeros((), bool)

        new = {
            "qp": qp2, "x": x2, "qdm": qdm1, "qds": qds1,
            "ym": ym1, "ys": ys1,
            "t": jnp.where(active, t_new, t),
            "rev": carry["rev"] + rev_inc,
            "acc_x": carry["acc_x"] + eff * x,
            "acc_ym": carry["acc_ym"] + eff * ym,
            "acc_ys": carry["acc_ys"] + eff * ys,
            "acc_qp": carry["acc_qp"] + eff * qp,
            "acc_qd": carry["acc_qd"] + eff * qd,
            "acc_t": carry["acc_t"] + eff,
            "completions": carry["completions"]
            + one * (f(is_md) + f(is_sd)),
            "arrivals": carry["arrivals"] + one * f(is_arr),
            "ab_p": carry["ab_p"] + one * f(is_ap),
            "ab_d": carry["ab_d"] + one * f(is_ad),
            "clip_steps": carry["clip_steps"] + f(clipped),
            "n_events": carry["n_events"] + f(ev),
        }
        return new, None

    return step


def _init_carry(I: int, dtype, telemetry=None) -> dict:
    z = jnp.zeros(I, dtype)
    s = jnp.zeros((), dtype)
    c = {
        "qp": z, "x": z, "qdm": z, "qds": z, "ym": z, "ys": z,
        "t": s, "rev": s,
        "acc_x": z, "acc_ym": z, "acc_ys": z, "acc_qp": z, "acc_qd": z,
        "acc_t": s,
        "completions": z, "arrivals": z, "ab_p": z, "ab_d": z,
        "clip_steps": s, "n_events": s,
    }
    if telemetry is not None:
        c.update(ctmc_probe_carry(telemetry, I=I, dtype=dtype))
    return c


_STATICS = ("n_steps", "gate_kind", "router_kind", "charging", "has_pw",
            "stepping", "telemetry")


def _run_core(params, key, *, n_steps, gate_kind, router_kind, charging,
              has_pw, stepping, telemetry=None):
    I = params["lam_tot"].shape[0]
    step = _build_step(params, key, gate_kind, router_kind, charging,
                       has_pw, stepping)
    if telemetry is not None:
        step = wrap_ctmc_step_probes(step, telemetry, params["horizon"])
    carry, _ = jax.lax.scan(
        step, _init_carry(I, params["lam_tot"].dtype, telemetry),
        jnp.arange(n_steps, dtype=jnp.uint32))
    return carry


run_uniformized = jax.jit(_run_core, static_argnames=_STATICS)


@partial(jax.jit, static_argnames=_STATICS)
def run_uniformized_batch(params, keys, *, n_steps, gate_kind, router_kind,
                          charging, has_pw, stepping, telemetry=None):
    """vmap of :func:`run_uniformized` over a leading batch of PRNG keys."""
    return jax.vmap(
        lambda k: _run_core(params, k, n_steps=n_steps, gate_kind=gate_kind,
                            router_kind=router_kind, charging=charging,
                            has_pw=has_pw, stepping=stepping,
                            telemetry=telemetry))(keys)


class UniformizedCTMC:
    """Batched uniformized simulator of the aggregate CTMC.

    Drop-in statistical replacement for :class:`CTMCSimulator` on the
    gate-and-route family: same classes/primitives/pricing/policy inputs,
    same :class:`CTMCResult` outputs, but replications run as one
    ``jax.vmap`` batch over PRNG keys.  ``horizon`` and ``warmup`` are
    fixed at construction because the scan length (``n_steps ~
    Lambda * horizon``) is a static compile-time quantity.

    ``stepping`` picks the scan form: ``"events"`` (default) runs one real
    transition per step with the conservation-law event budget
    (~``3 n lambda T`` steps); ``"ticks"`` runs the strict Lambda-clock
    uniformization (~``Lambda * T`` steps, self-loops included).
    ``cap_margin`` scales the abandonment-rate caps of the ticks-mode
    bound (larger = safer bound, more self-loops); ``steps_margin`` adds
    Poisson slack to the step count so the scan covers the horizon with
    overwhelming probability (check ``t_end == horizon`` on the result).
    """

    def __init__(self, classes: Sequence[WorkloadClass],
                 prim: ServicePrimitives, pricing: Pricing,
                 policy: PolicySpec, n: int, horizon: float,
                 warmup: float = 0.0, *, stepping: str = "events",
                 cap_margin: float = 6.0, steps_margin: float = 6.0,
                 n_steps: int | None = None, telemetry=None,
                 kv_xfer: float = 0.0):
        self.classes = tuple(classes)
        self.policy = policy
        self.n = int(n)
        self.I = len(self.classes)
        self.horizon = float(horizon)
        self.warmup = float(warmup)

        if stepping not in ("events", "ticks"):
            raise ValueError(f"stepping must be events|ticks, got {stepping!r}")
        self.stepping = stepping

        # KV-transfer charge (seconds per prompt token): folds into the
        # aggregate prefill service rate mu_p; the 0.0 default takes the
        # legacy expression in rates_for, keeping existing runs bitwise
        arr = rate_arrays(self.classes, prim, kv_xfer)
        bound = uniformization_bound(self.classes, prim, policy, self.n,
                                     cap_margin=cap_margin,
                                     kv_xfer=kv_xfer)
        self.Lambda = bound["Lambda"]
        self.M = int(bound["M"])
        if n_steps is not None:
            self.n_steps = int(n_steps)
        elif stepping == "ticks":
            lt = self.Lambda * self.horizon
            self.n_steps = int(math.ceil(
                lt + steps_margin * math.sqrt(lt) + 64))
        else:
            # pathwise: events <= 3 * arrivals, arrivals ~ Poisson(n lam T)
            at = float(self.n * arr["lam"].sum()) * self.horizon
            self.n_steps = int(math.ceil(
                3.0 * (at + steps_margin * math.sqrt(at)) + 64))

        self.gate_kind = _gate_kind(policy)
        self.router_kind = ("randomized" if policy.router == "randomized"
                            else "solo_first")
        self.charging = policy.charging
        pw_m, pw_s = policy.pool_weights_mixed, policy.pool_weights_solo
        if (pw_m is None) != (pw_s is None):
            raise ValueError("ctmc_jax needs both pool-weight vectors "
                             "or neither")
        self.has_pw = pw_m is not None

        dt = jnp.result_type(float)
        ones = np.ones(self.I)

        def a(v):
            return jnp.asarray(v, dtype=dt)

        gate = policy.gate
        self.params = {
            "lam_tot": a(self.n * arr["lam"]),
            "theta": a(arr["theta"]),
            "mu_p": a(arr["mu_p"]),
            "mu_m": a(arr["mu_m"]),
            "mu_s": a(arr["mu_s"]),
            "w": a([pricing.bundled_reward(c) for c in self.classes]),
            "w_pre": a([pricing.prefill_reward(c) for c in self.classes]),
            "w_dec": a([pricing.decode_reward(c) for c in self.classes]),
            "x_star": a(gate.x_star if isinstance(gate, OccupancyGate)
                        else ones),
            "qp_star": a(gate.qp_star if isinstance(gate, OccupancyGate)
                         else 0 * ones),
            "ratio": a(gate.ratio if isinstance(gate, PriorityRatioGate)
                       else ones),
            "p_s": a(policy.solo_prob if policy.solo_prob is not None
                     else ones),
            "pw_m": a(pw_m if pw_m is not None else ones),
            "pw_s": a(pw_s if pw_s is not None else ones),
            "n": a(self.n),
            "M": a(self.M),
            "cap_m": a(bound["cap_m"]),
            "cap_s": a(bound["cap_s"]),
            "qp_cap": a(bound["qp_cap"]),
            "qd_cap": a(bound["qd_cap"]),
            "Lambda": a(self.Lambda),
            "horizon": a(self.horizon),
            "warmup": a(self.warmup),
        }
        self._static = dict(n_steps=self.n_steps, gate_kind=self.gate_kind,
                            router_kind=self.router_kind,
                            charging=self.charging, has_pw=self.has_pw,
                            stepping=self.stepping,
                            telemetry=resolve_probe_spec(telemetry))
        self.telemetry = self._static["telemetry"]

    # -- raw (device array) interface -------------------------------------
    def _key(self, seed):
        if isinstance(seed, (int, np.integer)):
            return jax.random.PRNGKey(int(seed))
        return seed

    def run_raw(self, seed) -> dict:
        """One replication; returns the raw scan carry (device arrays)."""
        return run_uniformized(self.params, self._key(seed), **self._static)

    def run_batch_raw(self, seeds: Sequence, *, placement: str = "vmap",
                      shard: Optional[dict] = None) -> dict:
        """All replications in one batch; leaves gain a leading
        replication axis.

        ``placement`` picks the execution layout (see
        :mod:`repro.sweep.sharded`): ``"vmap"`` (default) is the
        single-device oracle, ``"shard_map"`` partitions the key batch
        over the devices' 1-D cells mesh (bitwise identical results),
        ``"single"`` falls back to one jitted run per seed.  ``shard``
        forwards tiling kwargs (``n_devices``,
        ``max_cells_per_device``, ``bytes_per_cell``,
        ``memory_budget``) to :func:`repro.sweep.sharded.run_sharded`.
        """
        if placement == "single":
            outs = [self.run_raw(s) for s in seeds]
            return {k: jnp.stack([o[k] for o in outs]) for k in outs[0]}
        keys = jnp.stack([self._key(s) for s in seeds])
        if placement == "vmap":
            return run_uniformized_batch(self.params, keys, **self._static)
        if placement == "shard_map":
            from repro.sweep.sharded import run_sharded

            static = dict(self._static)
            raw, self.shard_report = run_sharded(
                lambda p, k: _run_core(p, k, **static),
                self.params, keys, **(shard or {}))
            return raw
        raise ValueError(f"unknown placement {placement!r} (expected "
                         f"single|vmap|shard_map)")

    def telemetry_from_raw(self, raw: dict) -> dict:
        """Host-side probe report (:func:`extract_probes`) from a raw
        carry of a telemetry-enabled run.  The aggregate chain fills the
        trajectory probes only -- per-request latency histograms do not
        exist at the class-aggregate level."""
        if self.telemetry is None:
            raise ValueError("this UniformizedCTMC was built without "
                             "telemetry=; pass a ProbeSpec/True at init")
        return extract_probes(raw, self.telemetry, horizon=self.horizon,
                              n_servers=self.n)

    # -- CTMCResult interface ----------------------------------------------
    def _to_result(self, o: dict) -> CTMCResult:
        meas = max(float(o["acc_t"]), 1e-12)
        n = self.n
        return CTMCResult(
            t_end=float(o["t"]),
            revenue=float(o["rev"]),
            revenue_rate_per_server=float(o["rev"]) / (n * meas),
            completions=np.asarray(o["completions"], dtype=np.float64),
            arrivals=np.asarray(o["arrivals"], dtype=np.float64),
            abandons_p=np.asarray(o["ab_p"], dtype=np.float64),
            abandons_d=np.asarray(o["ab_d"], dtype=np.float64),
            avg_x=np.asarray(o["acc_x"]) / meas / n,
            avg_ym=np.asarray(o["acc_ym"]) / meas / n,
            avg_ys=np.asarray(o["acc_ys"]) / meas / n,
            avg_qp=np.asarray(o["acc_qp"]) / meas / n,
            avg_qd=np.asarray(o["acc_qd"]) / meas / n,
            n_events=int(o["n_events"]),
        )

    def results_from_raw(self, raw: dict) -> list:
        """Split a :meth:`run_batch_raw` carry into per-replication
        :class:`CTMCResult` objects."""
        host = {k: np.asarray(v) for k, v in raw.items()}
        reps = host["t"].shape[0]
        return [self._to_result({k: v[r] for k, v in host.items()})
                for r in range(reps)]

    def run(self, seed) -> CTMCResult:
        return self._to_result({k: np.asarray(v)
                                for k, v in self.run_raw(seed).items()})

    def run_batch(self, seeds: Sequence, *, placement: str = "vmap",
                  shard: Optional[dict] = None) -> list:
        return self.results_from_raw(
            self.run_batch_raw(seeds, placement=placement, shard=shard))
