"""JAX-batched iteration-level trace-replay engine (jit + vmap).

Same system as :class:`repro.serving.engine_sim.ClusterEngine` -- the
paper's calibrated per-server scheduling simulator (Section 6.2): each
logical server advances in iterations, a mixed iteration (one prefill
chunk of up to C tokens + co-resident decode streams) takes ``tau_mix =
alpha + beta * chunk`` seconds, a decode-only iteration ``tau_solo(K) =
a_s + b_s * K`` (K = resident KV tokens) -- re-expressed so the event
loop becomes a fixed-budget scanned step function and a replication
batch one ``jax.vmap`` over PRNG keys, following the
``repro.core.ctmc_jax`` playbook.  The Python :class:`ClusterEngine`
remains the semantics oracle; ``tests/test_engine_jax.py`` holds the two
engines to statistical equivalence on shared traces.

**Tensorized traces.**  Input is a :class:`repro.data.traces.TraceTensors`
(padded ``(rid, t, class, P, D, patience)`` arrays with a max-requests
cap).  Requests are re-numbered in arrival order, which makes every
queue a *pointer pair over a precomputed table*: arrivals are consumed
by one monotone cursor (arrival times are sorted), and each class's
FCFS prefill queue is a sliding window ``[qhead_i, qarr_i)`` over the
host-precomputed table of that class's rids in arrival order.  The
decode buffer is a ring of rids (pushes are monotone -- each request is
buffered at most once -- so the ring never wraps).  Per-server residency
lives in ``(n, B)`` slot arrays; per-request lifecycle state lives in
``(R,)`` arrays touched only by point gathers and a small, fixed number
of scatters.  One event costs ``O(n*B + B + I)`` *work*, independent of
the trace length ``R``; since a point-scatter costs a full array pass on
CPU XLA, the step is additionally organised to touch each ``(R,)`` array
at most once (all lifecycle transitions flush through ONE combined
scatter-max -- the state codes are ordered along the lifecycle, so max
composes even when a request transitions twice in one event).  This is
what makes the step competitive with (and ~10x faster than, batched)
the Python heap loop.

**One event per step.**  Each step advances to the next event -- the
earliest pending arrival or the earliest iteration boundary (``argmin``
over per-server ``t_next``; ties resolve arrival-first, matching the
Python heap's push order) -- and applies it branchlessly:

1. decode emissions for the finishing server's snapshot participants
   (a per-slot ``live`` flag replicates continuous-batching semantics:
   jobs placed mid-iteration wait for the next boundary),
2. prefill-chunk progress (tracked per *server* -- one active prefill
   each -- so it never touches the request axis); a finished prefill is
   pushed to the decode buffer (or per-server pending state for the
   ``immediate`` router),
3. decode dispatch.  At most ``freed-slots + 1 <= B + 1`` placements
   can happen per event (an invariant of the dispatch discipline), so
   for the deterministic global-buffer routers dispatch is ONE
   closed-form ranked assignment over a ``B+1`` window of the FCFS
   ring: servers contribute free slots in routing order to a cumulative
   array, ring jobs map FCFS rank ``j`` to the server covering slot
   ``j`` -- exactly the Python engine's fill-servers-in-order /
   jobs-in-FCFS-order loop with no sequential sub-steps.  The
   ``immediate`` and ``randomized`` routers keep a bounded placement
   loop (per-placement uniform server draws + EC.7 class weights, like
   the Python engine's rng usage),
4. at most one prefill admission via a branchless gate ``argmax``
   (occupancy deviation with queue-deviation tie-break, decode/prompt
   priority ratio, or the exact head-of-line class for FCFS -- exact,
   not the aggregate CTMC's proportional draw, because the queue heads
   are available here).  One admission per event suffices: the gate
   family maintains the invariant that after every event either no
   prefill slot is free or no admissible class waits, and each event
   frees at most one prefill slot or adds one waiting job,
5. one wake pass (slot snapshot + iteration-time computation) after
   admission -- the Python engine's step-5 order; a server the dispatch
   phase would have woken while idle which then drew the admission
   starts decode-only, its prefill waiting for the next boundary,
   exactly like the oracle.

**Iteration budget and loop form.**  The step budget is the minimum of
two *hard* bounds (no stochastic tail -- everything is deterministic
given the trace): the pathwise bound ``arrivals + sum_r ceil(P_r /
chunk_min) + sum_r D_r`` (every iteration advances a prefill chunk or
emits at least one decode token) and the clock bound ``arrivals + n *
(h_eff / tau_min + 1)`` (every iteration lasts at least ``tau_min =
min(alpha + beta, tau_solo)``).  ``loop="while"`` (default) runs the
step under ``jax.lax.while_loop`` capped at that budget but exiting as
soon as no event is pending before the horizon; ``loop="scan"`` runs
the strict fixed-shape ``jax.lax.scan`` over the full budget (useful
for profiling or step-coupled experiments -- the two forms are
bitwise-identical in their results, the scan just pays for its no-op
tail).  If a caller-supplied ``max_steps`` truncates the budget, the
engine reports ``budget_exhausted`` (next pending event still before
the horizon) -- detected, never silent.  ``docs/SIMULATORS.md`` carries
the derivation.

**Multi-event blocks (``k_events``).**  The scanned body can process
``k_events`` consecutive events per step (default 1 -- the historical
one-event body).  The k-unrolled block is *bitwise identical* to k
single-event steps (``tests/test_engine_diff.py`` pins this) because
every cross-event interaction inside a block goes through carry state
that is updated immediately, while the expensive ``(R,)``-array writes
are *deferred and merged*: lifecycle codes flush through one k-way
combined scatter-max per block (codes are monotone along the
lifecycle, so max composes), first/last-emission times through one
combined scatter-min/-max (write-only in-step), decode-buffer ring
pushes through one k-point scatter (in-block pops overlay the pending
pushes onto the ``B+1`` dispatch window), and the ``(R,)``
resident-token array is replaced by a dense ``(n, B)`` per-slot
counter (each request occupies exactly one decode slot exactly once,
so the counter carries the same information with zero request-axis
traffic).  A block whose horizon/budget exit lands mid-block simply
runs its remaining events as proven no-ops.  This cuts the per-event
``(R,)``-pass count from ~5 to ~4/k -- which is what the per-event
wall time is made of on CPU XLA -- for the deterministic global-buffer
routers; the immediate/randomized routers must keep their per-event
lifecycle reads and gain only the write-only deferrals.

**Documented deviations** from the Python oracle (all measure-zero or
deadline-only; the equivalence tests quantify them):

* deadline expiry is checked at pop time like the Python engine, but an
  expired pop consumes one of the event's bounded placements, and queue
  expiry drops at most one expired head per class per event (the Python
  engine drains all of them) -- identical for the default ``patience =
  inf`` traces (where the whole expiry machinery compiles away), a
  small lag otherwise;
* when several decodes are placed on one idle server in a single event,
  all of them join its first iteration (the Python engine wakes the
  server at the first placement, so later placements wait a boundary);
* the randomized router consumes a different PRNG stream (per-step
  ``fold_in`` draws vs. a shared ``numpy`` generator), so randomized
  policies match statistically, not bitwise;
* exact-tie tie-breaks (simultaneous events, equal-arrival FCFS heads)
  resolve by index rather than heap counter.

Not supported (use the Python engine): server failures/recoveries,
stragglers, the online controller (rolling-window replanning), and
``record_queues_every`` traces.  Replays beyond the host-padded-table
memory ceiling live in :mod:`repro.serving.engine_stream`
(:class:`StreamingEngineJAX`), which drives this same step function
over a compacted working-set window fed by trace chunks.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ctmc_jax import _categorical
from repro.core.policies import (FCFSGate, OccupancyGate, PolicySpec,
                                 PriorityRatioGate)
from repro.core.types import WorkloadClass
from repro.data.traces import TraceTensors, tensorize_trace
from repro.telemetry.probes import (extract_probes, hist_edges,
                                    probe_carry, resolve_probe_spec,
                                    wrap_engine_step_probes)

from .engine_sim import EngineConfig

__all__ = ["ClusterEngineJAX", "iteration_budget", "run",
           "run_engine", "run_engine_batch", "run_engine_multi"]

# request lifecycle (int32 codes carried through the scan)
_NOT_ARRIVED, _QUEUED, _PREFILL, _BUF, _DECODE, _DONE, _ABANDONED = range(7)

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
        f"engine_jax does not support gate {type(gate).__name__}; "
        "use the Python ClusterEngine")


def iteration_budget(tt: TraceTensors, cfg: EngineConfig, h_eff: float,
                     *, arrived: Optional[np.ndarray] = None) -> int:
    """Hard upper bound on events (arrivals + iteration completions).

    ``min(pathwise, clock)`` -- both bounds are deterministic given the
    trace, so no Poisson slack is needed (see the module docstring and
    ``docs/SIMULATORS.md`` for the derivation).
    """
    prim = cfg.prim
    if arrived is None:
        arrived = tt.valid & (tt.t <= h_eff)
    A = int(arrived.sum())
    P = tt.P[arrived].astype(np.float64)
    D = tt.D[arrived].astype(np.float64)
    if cfg.vllm_unchunked:
        chunks = np.ones_like(P)
    elif cfg.sarathi_budget:
        c_min = max(1, prim.chunk - (prim.batch_cap - 1))
        chunks = np.ceil(P / c_min)
    else:
        chunks = np.ceil(P / prim.chunk)
    pathwise = float(chunks.sum() + D.sum())
    m = cfg.iter_model
    if m is not None:
        # lower-bound the iteration time under the plugged model: affine
        # surfaces are minimal at (C=1, K=0); a table model's true min is
        # over its knot values (constant extrapolation beyond them)
        tau_min = min(m.tau_mix(1.0), m.tau_solo(0.0))
        if hasattr(m, "knots"):
            kn = m.knots()
            tau_min = min(min(kn["mix_y"]), min(kn["solo_y"]))
    elif cfg.fleet is not None:
        # fastest class lower-bounds every server's iteration time (the
        # KV-transfer charge only ever adds time, so it never loosens
        # this bound)
        fp = cfg.fleet.server_params(prim)
        tau_min = float(min((fp["alpha"] + fp["beta"]).min(),
                            fp["tau_solo"].min()))
    else:
        tau_min = min(prim.alpha + prim.beta, prim.tau_solo)
    clock = cfg.n_servers * (h_eff / tau_min + 1.0)
    return A + int(np.ceil(min(pathwise, clock))) + 16


_FFWD_JMAX = 64  # boundaries scanned per fast-forward window (per step)


def _build_step(params: dict, key, *, n: int, B: int, gate_kind: str,
                router_kind: str, charging: str, partition: str,
                sarathi: bool, unchunked: bool, prefill_only: bool,
                has_pw: bool, expiry: bool, model_kind: str = "affine",
                k_events: int = 1, fastforward: bool = False,
                telemetry=None):
    dtype = params["t_arr"].dtype
    # telemetry is a static: probes-off compiles the byte-identical
    # bare kernel.  All in-step probe work lives in the post-step
    # wrapper at the bottom of this builder; the latency histograms
    # need no hooks at all -- the ``t_first``/``t_last`` min/max marks
    # the step already maintains are bucketed once after the loop
    # (:func:`_fill_latency_hists`), keeping the probed step fusable.
    tlm = telemetry
    R = params["t_arr"].shape[0]
    I = params["x_star"].shape[0]
    W = B + 1  # placement bound per event: freed slots + the routed job
    sid = jnp.arange(n, dtype=jnp.int32)
    iota_I = jnp.arange(I, dtype=jnp.int32)
    iota_W = jnp.arange(W, dtype=jnp.int32)
    inf = jnp.asarray(jnp.inf, dtype)
    t_arr, cls = params["t_arr"], params["cls"]
    P, D, patience = params["P"], params["D"], params["patience"]
    # the ranked-assignment routers never read per-request lifecycle
    # state inside the step, so every ``st`` write can be deferred into
    # ONE combined scatter-max per step (a point-scatter costs a full
    # array pass on CPU XLA, so the scatter count on (R,) arrays is what
    # the step's wall time is made of)
    fast_st = router_kind in ("solo_first", "local_fcfs")
    need_tbuf = (expiry or router_kind == "immediate"
                 or (router_kind == "randomized" and has_pw))
    # k-event blocks additionally defer the write-only t_first/t_last
    # scatters and (fast routers) the buf-ring pushes across the whole
    # block, and swap the (R,) resident-token array for a dense (n, B)
    # per-slot counter -- see the module docstring; bitwise-identical
    # to k single-event steps
    multi = k_events > 1
    if fastforward and not (fast_st and model_kind == "affine"):
        raise ValueError("fastforward needs a deterministic global-buffer "
                         "router (solo_first/local_fcfs) and the affine "
                         "iteration-time model")
    dense_tout = fast_st and (multi or fastforward)

    def f(b):
        return b.astype(dtype)

    def rc(idx):
        return jnp.clip(idx, 0, R - 1)

    def used_of(slot_rid):
        return jnp.sum(f(slot_rid >= 0), axis=1)  # (n,)

    def cap_of(pf_rid):
        """Per-server decode-slot capacity given current prefill state."""
        has_pf = f(pf_rid >= 0)
        if partition == "none":
            return params["B"] - has_pf
        mixed = sid < params["Mi"]
        cap_mixed = (jnp.zeros(n, dtype) if prefill_only
                     else params["B"] - has_pf)
        return jnp.where(mixed, cap_mixed, params["B"])

    def place_into(c, srv_i, j, ok):
        """Scatter job ``j`` into the first empty slot of server
        ``srv_i`` (masked by ``ok``) and flip its lifecycle state.
        Used by the sequential (immediate / randomized) dispatchers."""
        row = c["slot_rid"][srv_i]
        slot = jnp.argmax(row < 0)
        c["slot_rid"] = c["slot_rid"].at[srv_i, slot].max(
            jnp.where(ok, j.astype(jnp.int32), -1))
        c["st"] = c["st"].at[rc(j)].max(jnp.where(ok, _DECODE, -1))
        if "srv" in c:
            c["srv"] = c["srv"].at[rc(j)].set(
                jnp.where(ok, srv_i.astype(jnp.int32), c["srv"][rc(j)]))
        return c

    def wake(c, now, active, force_solo):
        """Start an iteration on every non-busy server with work
        (snapshot semantics: resident decodes join, chunk is fixed).

        ``force_solo`` marks servers the Python engine would have woken
        *during* dispatch -- before the admission step could hand them a
        prefill -- so their iteration starts decode-only and the prefill
        waits for the next boundary, exactly like the oracle."""
        used = used_of(c["slot_rid"])
        has_pf = c["pf_rid"] >= 0
        do = active & ~c["busy"] & (has_pf | (used > 0))
        pl = c["pf_left"]  # per-server: one active prefill per server
        if unchunked:
            chn = pl
        elif sarathi:
            chn = jnp.clip(params["C"] - used, 0.0, pl)
        else:
            chn = jnp.minimum(pl, params["C"])
        chn = jnp.where(has_pf & ~force_solo, chn, 0.0)
        occupied = c["slot_rid"] >= 0
        src = rc(c["slot_rid"])
        pfr = rc(c["pf_rid"])
        tout_res = c["slot_tout"] if dense_tout else c["tout"][src]
        kv = (jnp.sum(jnp.where(occupied, P[src] + tout_res, 0.0),
                      axis=1)
              + jnp.where(has_pf, P[pfr] - pl, 0.0))
        if model_kind == "table":
            # piecewise-linear iteration-time surfaces over calibrated
            # knots (jnp.interp clamps beyond the knot range, matching
            # TableModel's constant extrapolation in the Python engine)
            tau = jnp.where(
                has_pf & (chn > 0),
                jnp.interp(chn, params["mix_x"], params["mix_y"]),
                jnp.interp(kv, params["solo_x"], params["solo_y"]))
        else:  # "affine": the historical expression, untouched
            tau = jnp.where(has_pf & (chn > 0),
                            params["alpha"] + params["beta"] * chn,
                            params["tau_solo"] + params["b_s"] * kv)
        # KV-transfer charge: the chunk that FINISHES a prefill ships the
        # whole KV cache to the decode pool and occupies the server for
        # kv_xfer * P extra seconds (DistServe-style handoff).  kv_xfer
        # is 0.0 without a fleet, so this adds an exact + 0.0 and the
        # homogeneous hot path stays bitwise-clean.
        fin = has_pf & (chn > 0.0) & (chn >= pl)
        tau = tau + f(fin) * (params["kv_xfer"] * P[pfr])
        c["chunk"] = jnp.where(do, chn, c["chunk"])
        c["t_next"] = jnp.where(do, now + tau, c["t_next"])
        c["busy"] = c["busy"] | do
        c["slot_live"] = c["slot_live"] | (do[:, None] & occupied)
        return c

    # stacked per-request constants so ffwd pays one gather where it
    # would otherwise pay two (class ids are tiny, exact in float32)
    DP2 = jnp.stack([D, P])
    AC2 = jnp.stack([t_arr, f(cls)])

    def ffwd(c):
        """Retire a batch of non-interacting events in closed form.

        Between two *interaction* events (an arrival, a decode
        completion, a prefill finish -- the only transitions that can
        change queue/dispatch/admission state, by the dispatch-window
        and one-admission-per-event invariants the step maintains),
        every busy server just runs iterations that emit decode tokens
        and advance prefill chunks.  Those boundary events are
        independent across servers and deterministic, so this block
        advances each batchable server over all its boundaries that lie
        strictly before every pending interaction (and before the next
        arrival, and at or before the horizon) in one shot: token
        counters move by ``j`` on the dense per-slot array, first-token
        times scatter once with the exact first-boundary time, and
        ``t_next`` lands on the closed-form partial sum of the
        iteration-time series (constant ``alpha + beta*chunk`` for a
        mid-prefill server; the arithmetic series ``tau_solo + b_s *
        (kv0 + i*L)`` for a decode server whose KV grows by ``L`` per
        iteration).  Results match the one-event path exactly up to
        float summation order (the partial sum replaces ``j`` chained
        additions); the event *sequence* is identical.  A server with a
        freshly-placed, not-yet-woken resident (``slot_live`` false) or
        an in-flight partial chunk boundary is simply not batchable
        this window and is processed by the normal path instead.
        """
        t0 = c["t_next"]  # (n,) first-boundary times (exact)
        occ = c["slot_rid"] >= 0
        L = jnp.sum(f(occ), axis=1)
        rr2 = rc(c["slot_rid"])
        dp = DP2[:, rr2]  # one gather serves both D and P lookups
        # tokens to the earliest resident completion (>= 1 by
        # invariant); a not-yet-woken resident poisons the min to -inf
        # so d > 0 doubles as the all-residents-live check
        d = jnp.min(jnp.where(occ,
                              jnp.where(c["slot_live"],
                                        dp[0] - c["slot_tout"], -inf),
                              inf), axis=1)
        has_pf = c["pf_rid"] >= 0
        pl, chn = c["pf_left"], c["chunk"]
        kv0 = jnp.sum(jnp.where(occ, dp[1] + c["slot_tout"], 0.0),
                      axis=1)
        tau_pf = params["alpha"] + params["beta"] * chn
        a_s, b_s = params["tau_solo"], params["b_s"]

        def T(j):  # time of boundary index j (j = 0 -> t_next)
            dec = j * a_s + b_s * (j * kv0 + L * j * (j - 1.0) / 2.0)
            return t0 + jnp.where(has_pf, j * tau_pf, dec)

        # first interaction boundary per server: earliest completion
        # (j = d-1) or the chunk that finishes the prefill
        jC = d - 1.0
        jF = jnp.ceil(pl / jnp.maximum(chn, 1.0)) - 1.0
        jint = jnp.where(has_pf, jnp.minimum(jC, jF), jC)
        okb = (c["busy"] & (d > 0.0)
               & jnp.where(has_pf, chn > 0, True))
        # step 4 admits at most ONE queued prefill per event, so a
        # waiting head plus an admission-capable server means the very
        # next event -- whatever it is -- performs an admission: every
        # boundary is then an interaction and the window must be empty.
        # (Dispatch needs no such guard: after any event the ring is
        # empty or decode capacity is, and neither changes in-window.)
        qlen0 = f(c["qarr"] - c["qhead"])
        no_pf0 = c["pf_rid"] < 0
        if partition == "none":
            canp0 = no_pf0 & (L < params["B"])
            if sarathi:
                canp0 = canp0 & (L < params["B"] - 1.0)
        else:
            canp0 = ((sid < params["Mi"]) & no_pf0
                     & (L <= params["B"] - 1.0))
        if gate_kind == "occupancy":
            waiting = (qlen0 >= 1) & (params["x_star"] > _EPS_TARGET)
        else:
            waiting = qlen0 >= 1
        blocked = canp0.any() & waiting.any()
        if expiry:  # lazy head-expiry also fires once per event
            blocked = blocked | (qlen0 >= 1).any()
        okb = okb & ~blocked
        jint = jnp.where(okb, jnp.maximum(jint, 0.0), 0.0)
        t_int = jnp.where(okb, T(jint), t0)  # non-batchable: t_next
        t_imin = t_int.min()
        # one lookahead gather serves both the next-arrival bound
        # (its first lane) and the arrival batch below
        a0 = c["aptr"]
        aw = a0 + jnp.arange(_FFWD_JMAX, dtype=a0.dtype)
        acw = AC2[:, rc(aw)]  # one gather serves t_arr and cls lookups
        taw = jnp.where(f(aw) < params["A"], acw[0], inf)
        ta0 = taw[0]
        # with no admission-capable server, an arrival merely joins its
        # class queue -- it cannot admit, dispatch, or wake anything --
        # so arrivals and boundaries commute and neither caps the other
        no_adm = (jnp.zeros((), bool) if expiry
                  else ~canp0.any())
        t_cap = jnp.where(no_adm, t_imin, jnp.minimum(ta0, t_imin))
        if "frontier" in params:
            # streamed replay: never batch past the next chunk's splice
            # point (its arrivals are not loaded yet, so ta0 is blind to
            # them); the segment loop stops there, ffwd must too
            t_cap = jnp.minimum(t_cap, params["frontier"])
        jj = jnp.arange(_FFWD_JMAX, dtype=dtype)[None, :]
        # (n,)-shaped surfaces (heterogeneous fleets) need the explicit
        # column axis; the scalar path emits the identical expressions
        a_sB = a_s[:, None] if jnp.ndim(a_s) else a_s
        b_sB = b_s[:, None] if jnp.ndim(b_s) else b_s
        Tj = (t0[:, None]
              + jnp.where(has_pf[:, None], jj * tau_pf[:, None],
                          jj * a_sB + b_sB * (jj * kv0[:, None]
                                              + L[:, None] * jj
                                              * (jj - 1.0) / 2.0)))
        # batchable boundaries: strictly before every interaction and
        # the next arrival (arrival-first tie-break preserved), at or
        # before the horizon (events at h_eff are processed), strictly
        # before this server's own interaction boundary
        okj = (Tj < t_cap) & (Tj <= params["h_eff"]) & (jj < jint[:, None])
        j_s = jnp.where(okb, jnp.sum(f(okj), axis=1), 0.0)
        adv = j_s > 0
        # post-window state, computed exactly like the per-boundary wake
        pl2 = pl - j_s * chn
        chn2 = pl2 if unchunked else (
            jnp.clip(params["C"] - L, 0.0, pl2) if sarathi
            else jnp.minimum(pl2, params["C"]))
        tau2 = jnp.where(has_pf, params["alpha"] + params["beta"] * chn2,
                         a_s + b_s * (kv0 + j_s * L))
        # finishing-chunk KV-transfer charge, mirroring wake exactly
        # (window boundaries jj < jint <= jF are never finishing chunks,
        # so only the post-window iteration can carry the charge)
        fin2 = has_pf & (chn2 > 0.0) & (chn2 >= pl2)
        tau2 = tau2 + f(fin2) * (params["kv_xfer"] * P[rc(c["pf_rid"])])
        t_last_b = T(j_s - 1.0)  # last batched boundary time
        c["t_next"] = jnp.where(adv, t_last_b + tau2, c["t_next"])
        c["pf_left"] = jnp.where(adv & has_pf, pl2, c["pf_left"])
        c["chunk"] = jnp.where(adv & has_pf, chn2, c["chunk"])
        emit = occ & adv[:, None]
        c["slot_tout"] = c["slot_tout"] + f(emit) * j_s[:, None]
        c["t_first"] = c["t_first"].at[rr2].min(
            jnp.where(emit, t0[:, None], inf))
        nb = jnp.sum(j_s)
        c["n_iters"] = c["n_iters"] + nb
        c["n_events"] = c["n_events"] + nb
        c["t"] = jnp.maximum(c["t"], jnp.where(adv, t_last_b,
                                               -jnp.inf).max())
        if not expiry:
            # queue-only arrival batch: every arrival strictly before
            # the earliest pending interaction (they stay QUEUED -- no
            # admission is possible until a server frees up, which is
            # itself an interaction).  t_arr is sorted, so the mask is
            # a prefix of the lookahead window.
            okm = no_adm & (taw < t_imin)
            m_arr = jnp.sum(jnp.where(okm, 1, 0))
            c["aptr"] = a0 + m_arr.astype(a0.dtype)
            c["st"] = c["st"].at[rc(aw)].max(jnp.where(okm, _QUEUED, -1))
            c["qarr"] = c["qarr"].at[acw[1].astype(jnp.int32)].add(
                jnp.where(okm, 1, 0))
            c["n_events"] = c["n_events"] + f(m_arr)
            c["t"] = jnp.maximum(c["t"],
                                 jnp.where(okm, taw, -jnp.inf).max())
        return c

    def event(c, idx, dfr):
        # ``dfr`` holds the cross-event deferred (R,)-scatter buffers of
        # the enclosing k-block (None in the single-event body)
        u = (jax.random.uniform(jax.random.fold_in(key, idx),
                                (2 * W + 1,), dtype=dtype)
             if router_kind == "randomized" else None)
        st_idx, st_val = [], []  # deferred combined scatter (fast_st)

        def st_max(c, idx_, val_):
            if fast_st:
                tgt_i = dfr["st_i"] if multi else st_idx
                tgt_v = dfr["st_v"] if multi else st_val
                tgt_i.append(jnp.atleast_1d(idx_.astype(jnp.int32)))
                tgt_v.append(jnp.atleast_1d(val_.astype(jnp.int32)))
            else:
                c["st"] = c["st"].at[idx_].max(val_)
            return c

        def mark_first(c, idx_, val_):
            # t_first is write-only in-step: scatter-min defers k-wide
            if multi:
                dfr["tf_i"].append(jnp.atleast_1d(idx_))
                dfr["tf_v"].append(jnp.atleast_1d(val_))
            else:
                c["t_first"] = c["t_first"].at[idx_].min(val_)
            return c

        def mark_last(c, idx_, val_):
            if multi:
                dfr["tl_i"].append(jnp.atleast_1d(idx_))
                dfr["tl_v"].append(jnp.atleast_1d(val_))
            else:
                c["t_last"] = c["t_last"].at[idx_].max(val_)
            return c

        # ---- next event: earliest arrival vs earliest iteration end ----
        ap = c["aptr"]
        ta = jnp.where(f(ap) < params["A"], t_arr[rc(ap)], inf)
        se = jnp.argmin(c["t_next"])
        tsv = c["t_next"][se]
        now = jnp.minimum(ta, tsv)
        active = now <= params["h_eff"]
        if "frontier" in params:
            # streamed replay: an event at/after the next chunk's splice
            # point could interact with arrivals not loaded yet
            active = active & (now < params["frontier"])
        is_arr = active & (ta <= tsv)  # heap pushes arrivals first: ties
        is_iter = active & ~is_arr     # resolve arrival-before-iteration

        # ---- arrival: advance the cursor, push to the class queue ------
        ca = cls[rc(ap)]
        c = st_max(c, rc(ap), jnp.where(is_arr, _QUEUED, -1))
        c["qarr"] = c["qarr"] + jnp.where(is_arr & (iota_I == ca), 1, 0)
        c["aptr"] = ap + jnp.where(is_arr, 1, 0)

        # ---- iteration end on server `se` (small per-server state is
        #      updated elementwise so the whole block fuses) -------------
        at_se = sid == se
        c["busy"] = c["busy"] & ~(at_se & is_iter)
        c["t_next"] = jnp.where(at_se & is_iter, inf, c["t_next"])
        # 1) snapshot decodes emit one token each (B-sized gathers; the
        #    scatters use add/min/max so clip-aliased empty slots -- all
        #    mapped to index 0 -- contribute identities, never clobbers)
        row = c["slot_rid"][se]
        rr = rc(row)
        live = is_iter & (row >= 0) & c["slot_live"][se]
        if dense_tout:  # per-slot counter: zero request-axis traffic
            tout_new = c["slot_tout"][se] + 1.0
            c["slot_tout"] = c["slot_tout"] + f(at_se[:, None]
                                                & live[None, :])
        else:
            tout_new = c["tout"][rr] + 1.0  # live slots hold distinct rids
            c["tout"] = c["tout"].at[rr].add(f(live))
        c = mark_first(c, rr, jnp.where(live, now, inf))
        c = mark_last(c, rr, jnp.where(live, now, -inf))
        done = live & (tout_new >= D[rr])
        if charging == "separate":
            reward = params["c_d"] * D[rr]
        else:
            reward = params["c_p"] * P[rr] + params["c_d"] * D[rr]
        c["rev"] = c["rev"] + jnp.sum(jnp.where(done, reward, 0.0))
        c = st_max(c, rr, jnp.where(done, _DONE, -1))
        if "srv" in c:
            c["srv"] = c["srv"].at[rr].min(
                jnp.where(done, -1, jnp.iinfo(jnp.int32).max))
        done_row = at_se[:, None] & done[None, :]
        c["slot_rid"] = jnp.where(done_row, -1, c["slot_rid"])
        c["slot_live"] = c["slot_live"] & ~done_row
        # 2) prefill-chunk progress + routing of a finished prefill
        #    (prefill-left is per-server: one active prefill per server)
        pf = c["pf_rid"][se]
        has_pf = is_iter & (pf >= 0)
        pfc = rc(pf)
        pln = c["pf_left"][se] - c["chunk"][se]
        c["pf_left"] = c["pf_left"] - jnp.where(at_se & has_pf,
                                                c["chunk"][se], 0.0)
        pf_done = has_pf & (pln <= 0)
        if charging == "separate":
            c["rev"] = c["rev"] + jnp.where(pf_done,
                                            params["c_p"] * P[pfc], 0.0)
        if need_tbuf:
            c["t_buf"] = c["t_buf"].at[pfc].set(
                jnp.where(pf_done, now, c["t_buf"][pfc]))
        c = st_max(c, pfc, jnp.where(pf_done, _BUF, -1))
        c["X"] = c["X"] - jnp.where(pf_done & (iota_I == cls[pfc]),
                                    1.0, 0.0)
        c["pf_rid"] = jnp.where(at_se & pf_done, -1, c["pf_rid"])
        if router_kind == "randomized":
            go_solo = u[0] <= params["p_solo"][cls[pfc]]
            c["pool"] = c["pool"].at[pfc].set(
                jnp.where(pf_done, jnp.where(go_solo, 0, 1),
                          c["pool"][pfc]))
            if not has_pw:  # pool FCFS rings
                for pid, ring in ((0, "buf_s"), (1, "buf_m")):
                    push = pf_done & (c["pool"][pfc] == pid)
                    tl = c[f"{ring}_tl"]
                    c[ring] = c[ring].at[tl].max(jnp.where(push, pf, -1))
                    c[f"{ring}_tl"] = tl + jnp.where(push, 1, 0)
        elif router_kind == "immediate":
            # stays pending on `se`: mark the target in srv
            c["srv"] = c["srv"].at[pfc].set(
                jnp.where(pf_done, se.astype(jnp.int32), c["srv"][pfc]))
        else:  # single global FCFS ring (solo_first / local_fcfs)
            tl = c["buf_tl"]
            if multi:
                # defer the (R+W,) ring write; `buf_tl` (a scalar) still
                # advances immediately, and in-block pops overlay the
                # pending pushes onto the dispatch window below.  A
                # masked push (-1) may share its index with a later real
                # one -- the flush scatter-max composes them.
                dfr["push_i"].append(tl)
                dfr["push_v"].append(jnp.where(pf_done, pf, -1))
            else:
                c["buf"] = c["buf"].at[tl].max(jnp.where(pf_done, pf, -1))
            c["buf_tl"] = tl + jnp.where(pf_done, 1, 0)

        # 3) decode dispatch.  For the deterministic global-buffer routers
        #    this is one closed-form ranked assignment over a W-window of
        #    the FCFS ring (at most freed-slots + 1 <= W placements can
        #    happen per event): servers contribute free slots in routing
        #    order via a cumulative array, ring jobs map rank j to the
        #    server covering slot j -- exactly the Python engine's
        #    fill-servers-in-order / jobs-in-FCFS-order loop.
        busy_pre = c["busy"]  # dispatch-time idleness (se already cleared)
        if router_kind in ("solo_first", "local_fcfs"):
            hd, tl = c["buf_hd"], c["buf_tl"]
            win = jax.lax.dynamic_slice(c["buf"], (hd,), (W,))
            if multi:  # overlay this block's not-yet-flushed ring pushes
                for ti, tv in zip(dfr["push_i"], dfr["push_v"]):
                    win = jnp.where(hd + iota_W == ti,
                                    jnp.maximum(win, tv), win)
            jw = rc(win)
            valid = (hd + iota_W < tl) & is_iter
            if expiry:
                expired = valid & (now - c["t_buf"][jw] > patience[jw])
            else:  # patience == inf everywhere: nothing ever expires
                expired = jnp.zeros(W, bool)
            pe = valid & ~expired  # placeable
            erank = jnp.cumsum(f(pe)) - f(pe)  # exclusive FCFS rank
            free = jnp.maximum(cap_of(c["pf_rid"])
                               - used_of(c["slot_rid"]), 0.0)
            free_sorted = free[params["perm_srv"]]
            cumfree = jnp.cumsum(free_sorted)
            totfree = cumfree[-1]
            consumed = valid & (erank < totfree)  # popped (placed/expired)
            place = pe & (erank < totfree)
            pos = jnp.searchsorted(cumfree, erank, side="right")
            server = params["perm_srv"][jnp.clip(pos, 0, n - 1)]
            within = erank - jnp.where(pos > 0,
                                       cumfree[jnp.maximum(pos - 1, 0)],
                                       0.0)
            # k-th empty physical slot of each server (stable sort puts
            # empty slots first, in index order)
            esort = jnp.argsort(c["slot_rid"] >= 0, axis=1)
            slot = esort[server, jnp.clip(within.astype(jnp.int32),
                                          0, B - 1)]
            c["slot_rid"] = c["slot_rid"].at[server, slot].max(
                jnp.where(place, win, -1))
            if dense_tout:  # fresh occupant: reset the per-slot counter
                c["slot_tout"] = c["slot_tout"].at[server, slot].min(
                    jnp.where(place, 0.0, jnp.inf))
            c = st_max(c, jw,
                       jnp.where(place, _DECODE,
                                 jnp.where(consumed & expired,
                                           _ABANDONED, -1)))
            c["buf_hd"] = hd + jnp.sum(jnp.where(consumed, 1, 0))
            c["abandons"] = c["abandons"] + jnp.sum(f(consumed & expired))
            placed_srv = jnp.zeros(n, bool).at[server].max(place)
        elif router_kind == "immediate":
            # pending jobs live as BUF with srv == se; FCFS by t_buf
            placed_any = jnp.zeros((), bool)
            for k in range(W):
                cap_se = cap_of(c["pf_rid"])[se]
                used_se = used_of(c["slot_rid"])[se]
                elig = (c["st"] == _BUF) & (c["srv"] == se)
                j = jnp.argmin(jnp.where(elig, c["t_buf"], inf))
                do = is_iter & elig.any() & (used_se < cap_se)
                expired = now - c["t_buf"][j] > patience[j]
                c["st"] = c["st"].at[j].max(
                    jnp.where(do & expired, _ABANDONED, -1))
                c["abandons"] = c["abandons"] + f(do & expired)
                c = place_into(c, se, j, do & ~expired)
                placed_any = placed_any | (do & ~expired)
            placed_srv = at_se & placed_any
        else:  # randomized: solo pool drains first; uniform server draw
            solo_srv = sid >= params["Mi"]
            placed_srv = jnp.zeros(n, bool)
            for k in range(W):
                u1, u2 = u[2 * k + 1], u[2 * k + 2]
                cap = cap_of(c["pf_rid"])
                used = used_of(c["slot_rid"])
                free = cap - used > 0
                free_s = solo_srv & free
                free_m = ~solo_srv & free
                if has_pw:  # EC.7 class weights need an in-buffer scan
                    elig_s = (c["st"] == _BUF) & (c["pool"] == 0)
                    elig_m = (c["st"] == _BUF) & (c["pool"] == 1)
                    can_s = free_s.any() & elig_s.any()
                    use_solo = can_s
                    do = is_iter & (can_s | (free_m.any() & elig_m.any()))
                    pool_elig = jnp.where(use_solo, elig_s, elig_m)
                    j_fcfs = jnp.argmin(
                        jnp.where(pool_elig, c["t_buf"], inf))
                    pw = jnp.where(use_solo, params["pw_s"],
                                   params["pw_m"])
                    present = jnp.zeros(I, dtype).at[cls].add(
                        f(pool_elig)) > 0
                    w = jnp.maximum(pw, 0.0) * f(present)
                    ci = _categorical(u1, w)
                    j_w = jnp.argmin(jnp.where(
                        pool_elig & (cls == ci), c["t_buf"], inf))
                    j = jnp.where(w.sum() > 0, j_w, j_fcfs)
                    pop = do & (c["st"][j] == _BUF)  # guard no-op lanes
                    expired = now - c["t_buf"][j] > patience[j]
                    c["st"] = c["st"].at[j].max(
                        jnp.where(pop & expired, _ABANDONED, -1))
                else:  # plain pool FCFS: ring heads
                    can_s = (free_s.any()
                             & (c["buf_s_hd"] < c["buf_s_tl"]))
                    can_m = (free_m.any()
                             & (c["buf_m_hd"] < c["buf_m_tl"]))
                    use_solo = can_s
                    do = is_iter & (can_s | can_m)
                    hd_s, hd_m = c["buf_s_hd"], c["buf_m_hd"]
                    j = jnp.where(use_solo, c["buf_s"][rc(hd_s)],
                                  c["buf_m"][rc(hd_m)])
                    pop = do
                    c["buf_s_hd"] = hd_s + jnp.where(pop & use_solo, 1, 0)
                    c["buf_m_hd"] = hd_m + jnp.where(pop & ~use_solo, 1, 0)
                    if expiry:
                        expired = (now - c["t_buf"][rc(j)]
                                   > patience[rc(j)])
                    else:
                        expired = jnp.zeros((), bool)
                    c["st"] = c["st"].at[rc(j)].max(
                        jnp.where(pop & expired, _ABANDONED, -1))
                pool_free = jnp.where(use_solo, free_s, free_m)
                sv = _categorical(u2, f(pool_free))
                c["abandons"] = c["abandons"] + f(pop & expired)
                c = place_into(c, sv, j, pop & ~expired)
                placed_srv = placed_srv | ((sid == sv) & pop & ~expired)

        # 4) at most one prefill admission (gate family invariant)
        heads = params["class_rids"][iota_I, rc(c["qhead"])]
        qlen = f(c["qarr"] - c["qhead"])
        if expiry:
            # lazy head expiry (at most one head per class per event)
            hexp = (active & (qlen > 0)
                    & (now - t_arr[rc(heads)] > patience[rc(heads)]))
            c = st_max(c, rc(heads), jnp.where(hexp, _ABANDONED, -1))
            c["qhead"] = c["qhead"] + jnp.where(hexp, 1, 0)
            c["abandons"] = c["abandons"] + jnp.sum(f(hexp))
            heads = params["class_rids"][iota_I, rc(c["qhead"])]
            qlen = f(c["qarr"] - c["qhead"])

        used2 = used_of(c["slot_rid"])
        no_pf = c["pf_rid"] < 0
        if partition == "none":
            if router_kind == "immediate":
                pend = _count_pending(c, n, dtype)
                canp = no_pf & (used2 + pend < params["B"])
            else:
                canp = no_pf & (used2 < params["B"])
            if sarathi:
                canp = canp & (used2 < params["B"] - 1)
        else:
            mixed = sid < params["Mi"]
            if router_kind == "immediate":
                pend = _count_pending(c, n, dtype)
                capm = (jnp.zeros(n, dtype) if prefill_only
                        else jnp.full(n, params["B"], dtype))
                canp = mixed & no_pf & (used2 + pend < capm)
            else:
                canp = mixed & no_pf & (used2 <= params["B"] - 1)
        canp = canp & active
        tgt = jnp.argmin(jnp.where(canp, sid, 2 * n))  # first free server
        if gate_kind == "occupancy":
            gmask = (qlen >= 1) & (params["x_star"] > _EPS_TARGET)
            xi = ((c["X"] + 1.0 - params["n_f"] * params["x_star"])
                  / jnp.maximum(params["x_star"], 1e-30))
            keyv = jnp.where(gmask, xi, inf)
            tie = gmask & (keyv == keyv.min())
            delta = qlen - params["n_f"] * params["qp_star"]
            cand = jnp.argmax(jnp.where(tie, delta, -inf))
            can = gmask.any()
        elif gate_kind == "priority":
            gmask = qlen >= 1
            cand = jnp.argmax(jnp.where(gmask, params["ratio"], -inf))
            can = gmask.any()
        else:  # fcfs: exact head-of-line class (oldest waiting request)
            cand = jnp.argmin(jnp.where(qlen >= 1, heads, R))
            can = (qlen >= 1).any()
        admit = canp.any() & can
        jr = heads[cand]
        c = st_max(c, rc(jr), jnp.where(admit, _PREFILL, -1))
        if "srv" in c:
            c["srv"] = c["srv"].at[rc(jr)].set(
                jnp.where(admit, tgt.astype(jnp.int32), c["srv"][rc(jr)]))
        c["qhead"] = c["qhead"] + jnp.where(admit & (iota_I == cand), 1, 0)
        c["X"] = c["X"] + jnp.where(admit & (iota_I == cand), 1.0, 0.0)
        c["pf_rid"] = jnp.where(admit & (sid == tgt),
                                jr.astype(jnp.int32), c["pf_rid"])
        c["pf_left"] = jnp.where(admit & (sid == tgt), P[rc(jr)],
                                 c["pf_left"])

        # flush the deferred lifecycle transitions in ONE scatter-max
        # (codes are ordered along the lifecycle, so max composes even
        # when one request transitions twice in a single event); k-event
        # blocks flush once per block instead
        if fast_st and not multi:
            c["st"] = c["st"].at[jnp.concatenate(st_idx)].max(
                jnp.concatenate(st_val))

        # single wake pass, post-admission (the Python engine's step-5
        # order).  A server the dispatch phase woke while idle -- which
        # then drew the admission -- starts decode-only: its prefill
        # joined after the Python wake and waits for the next boundary.
        force_solo = placed_srv & ~busy_pre & admit & (sid == tgt)
        c = wake(c, now, active, force_solo)

        c["t"] = jnp.where(active, now, c["t"])
        c["n_iters"] = c["n_iters"] + f(is_iter)
        c["n_events"] = c["n_events"] + f(active)
        # early-exit flag: is another event pending before the horizon?
        ta2 = jnp.where(f(c["aptr"]) < params["A"],
                        t_arr[rc(c["aptr"])], inf)
        c["alive"] = jnp.minimum(ta2, c["t_next"].min()) <= params["h_eff"]
        return c

    def finish(step_fn):
        if tlm is None:
            return step_fn
        return wrap_engine_step_probes(step_fn, tlm, params)

    if not multi:
        def step(carry, idx):
            c = dict(carry)
            c["n_loop"] = c["n_loop"] + f(c["alive"])
            if fastforward:
                c = ffwd(c)
            return event(c, idx, None)

        return finish(step)

    def step(carry, idx):
        # idx is the BLOCK index; events keep their global index so the
        # randomized router's fold_in stream is identical at every k
        c = dict(carry)
        c["n_loop"] = c["n_loop"] + f(c["alive"])
        if fastforward:
            c = ffwd(c)
        dfr = {k2: [] for k2 in ("st_i", "st_v", "tf_i", "tf_v",
                                 "tl_i", "tl_v", "push_i", "push_v")}
        base = idx * jnp.uint32(k_events)
        for j in range(k_events):
            c = event(c, base + jnp.uint32(j), dfr)
        # one combined flush per (R,) array for the whole block: max/min
        # compose across events exactly like across transitions
        if fast_st:
            c["st"] = c["st"].at[jnp.concatenate(dfr["st_i"])].max(
                jnp.concatenate(dfr["st_v"]))
            c["buf"] = c["buf"].at[jnp.stack(dfr["push_i"])].max(
                jnp.stack(dfr["push_v"]))
        c["t_first"] = c["t_first"].at[jnp.concatenate(dfr["tf_i"])].min(
            jnp.concatenate(dfr["tf_v"]))
        c["t_last"] = c["t_last"].at[jnp.concatenate(dfr["tl_i"])].max(
            jnp.concatenate(dfr["tl_v"]))
        return c

    return finish(step)


def _count_pending(c, n, dtype):
    """Pending-local counts for the immediate router (O(R) scan; only
    compiled into the immediate/sarathi variant)."""
    return jnp.zeros(n, dtype).at[jnp.clip(c["srv"], 0, n - 1)].add(
        (c["st"] == _BUF).astype(dtype))


def _init_carry(R: int, n: int, B: int, I: int, dtype,
                router_kind: str, has_pw: bool, expiry: bool,
                k_events: int = 1, fastforward: bool = False,
                telemetry=None) -> dict:
    W = B + 1
    c = {
        "st": jnp.zeros(R, jnp.int32),
        "tout": jnp.zeros(R, dtype),
        "t_first": jnp.full(R, jnp.inf, dtype),
        "t_last": jnp.full(R, -jnp.inf, dtype),  # max-scatter identity
        "slot_rid": jnp.full((n, B), -1, jnp.int32),
        "slot_live": jnp.zeros((n, B), bool),
        "pf_rid": jnp.full(n, -1, jnp.int32),
        "pf_left": jnp.zeros(n, dtype),
        "busy": jnp.zeros(n, bool),
        "t_next": jnp.full(n, jnp.inf, dtype),
        "chunk": jnp.zeros(n, dtype),
        "aptr": jnp.zeros((), jnp.int32),
        "qhead": jnp.zeros(I, jnp.int32),
        "qarr": jnp.zeros(I, jnp.int32),
        "X": jnp.zeros(I, dtype),
        "t": jnp.zeros((), dtype),
        "rev": jnp.zeros((), dtype),
        "n_iters": jnp.zeros((), dtype),
        "n_events": jnp.zeros((), dtype),
        "n_loop": jnp.zeros((), dtype),  # loop steps (batching factor)
        "abandons": jnp.zeros((), dtype),
        "alive": jnp.ones((), bool),
    }
    if (expiry or router_kind == "immediate"
            or (router_kind == "randomized" and has_pw)):
        c["t_buf"] = jnp.full(R, jnp.inf, dtype)
    if router_kind in ("solo_first", "local_fcfs"):
        # +W slack so the dispatch window never clamps its start index
        c["buf"] = jnp.full(R + W, -1, jnp.int32)
        c["buf_hd"] = jnp.zeros((), jnp.int32)
        c["buf_tl"] = jnp.zeros((), jnp.int32)
        if k_events > 1 or fastforward:
            # dense per-slot token counter (see _build_step)
            del c["tout"]
            c["slot_tout"] = jnp.zeros((n, B), dtype)
    elif router_kind == "randomized" and not has_pw:
        for ring in ("buf_s", "buf_m"):
            c[ring] = jnp.full(R + W, -1, jnp.int32)
            c[f"{ring}_hd"] = jnp.zeros((), jnp.int32)
            c[f"{ring}_tl"] = jnp.zeros((), jnp.int32)
    if router_kind == "immediate":
        c["srv"] = jnp.full(R, -1, jnp.int32)
    if router_kind == "randomized":
        c["pool"] = jnp.full(R, -1, jnp.int32)
    if telemetry is not None:
        # fixed-shape probe arrays under tlm_ keys: _summary never
        # reads them, so the non-telemetry outputs stay bitwise equal
        c.update(probe_carry(telemetry, n=n, I=I, dtype=dtype))
    return c


def _fill_latency_hists(carry: dict, t_arr, spec) -> dict:
    """Bucket the per-request latency marks into ``tlm_ttft``/``tlm_e2e``.

    The step already maintains ``t_first`` (min-scatter of every
    emission time) and ``t_last`` (max-scatter; equals the completion
    time for ``_DONE`` rows), so TTFT = ``t_first - t_arr`` and E2E =
    ``t_last - t_arr`` are exact per-request latencies and ONE
    searchsorted + scatter after the loop observes each request exactly
    once -- event-for-event what per-step histogram hooks would record,
    at none of their per-step fusion-breaking cost (the < 10% overhead
    contract of docs/OBSERVABILITY.md).  Rows that never emitted
    (``t_first`` infinite; includes padding) and rows not ``_DONE``
    carry zero weight; their NaN/out-of-band differences still land on
    a valid bucket index, so the masked adds are no-ops.
    """
    dt = t_arr.dtype
    edges = jnp.asarray(hist_edges(spec), dt)
    c = dict(carry)
    hb = jnp.searchsorted(edges, c["t_first"] - t_arr)
    c["tlm_ttft"] = c["tlm_ttft"].at[hb].add(
        jnp.isfinite(c["t_first"]).astype(dt))
    hb = jnp.searchsorted(edges, c["t_last"] - t_arr)
    c["tlm_e2e"] = c["tlm_e2e"].at[hb].add(
        (c["st"] == _DONE).astype(dt))
    return c


_STATICS = ("n_steps", "n", "B", "gate_kind", "router_kind", "charging",
            "partition", "sarathi", "unchunked", "prefill_only", "has_pw",
            "expiry", "loop", "model_kind", "k_events", "fastforward",
            "telemetry")


def _run_core(params, key, *, n_steps, n, B, gate_kind, router_kind,
              charging, partition, sarathi, unchunked, prefill_only,
              has_pw, expiry, loop="while", model_kind="affine",
              k_events=1, fastforward=False, telemetry=None):
    step = _build_step(params, key, n=n, B=B, gate_kind=gate_kind,
                       router_kind=router_kind, charging=charging,
                       partition=partition, sarathi=sarathi,
                       unchunked=unchunked, prefill_only=prefill_only,
                       has_pw=has_pw, expiry=expiry, model_kind=model_kind,
                       k_events=k_events, fastforward=fastforward,
                       telemetry=telemetry)
    R = params["t_arr"].shape[0]
    I = params["x_star"].shape[0]
    init = _init_carry(R, n, B, I, params["t_arr"].dtype,
                       router_kind, has_pw, expiry, k_events, fastforward,
                       telemetry)
    # the loop iterates over k-event BLOCKS; a final partial block runs
    # its overhang as proven no-op events (is_arr/is_iter/admit all
    # force False once no event is pending)
    n_blocks = -(-int(n_steps) // int(k_events))
    if loop == "scan":  # strict fixed-shape form (profiling / coupling)
        def body(carry, idx):
            return step(carry, idx), None

        carry, _ = jax.lax.scan(body, init,
                                jnp.arange(n_blocks, dtype=jnp.uint32))
        if telemetry is not None:
            carry = _fill_latency_hists(carry, params["t_arr"], telemetry)
        return carry
    # early-exit form: same step, same budget cap, but the loop stops as
    # soon as no event is pending before the horizon (the scan form pays
    # for its no-op tail; this one does not)
    def cond(state):
        carry, i = state
        return carry["alive"] & (i < n_blocks)

    def body(state):
        carry, i = state
        return step(carry, i.astype(jnp.uint32)), i + 1

    carry, _ = jax.lax.while_loop(
        cond, body, (init, jnp.zeros((), jnp.int32)))
    if telemetry is not None:
        carry = _fill_latency_hists(carry, params["t_arr"], telemetry)
    return carry


run_engine = jax.jit(_run_core, static_argnames=_STATICS)


@partial(jax.jit, static_argnames=_STATICS)
def run_engine_batch(params, keys, **statics):
    """vmap of :func:`run_engine` over a leading batch of PRNG keys."""
    return jax.vmap(lambda k: _run_core(params, k, **statics))(keys)


@partial(jax.jit, static_argnames=_STATICS)
def run_engine_multi(params, keys, **statics):
    """vmap over a leading *instance* axis of params AND keys.

    The instance axis can carry anything that only changes traced
    parameters: a DistServe split scan (instances differ in ``Mi``), a
    set of equal-shape traces replayed in lockstep (pad them to one
    length with ``tensorize_trace(pad_to=...)``), or perturbed
    primitives.  All instances share one compile; statics (shapes,
    router/gate kinds) must match.
    """
    return jax.vmap(lambda p, k: _run_core(p, k, **statics))(params, keys)


# the streamed-replay segment loop has no fixed scan length (it stops at
# the chunk frontier) and always early-exits, so n_steps/loop drop out
_SEG_STATICS = tuple(s for s in _STATICS if s not in ("n_steps", "loop"))


@partial(jax.jit, static_argnames=_SEG_STATICS)
def _run_segment(params, key, carry, i0, budget, **statics):
    """Run engine steps from ``carry`` until the chunk frontier, the
    horizon or the step budget -- the streamed-replay segment loop
    (:class:`repro.serving.engine_stream.StreamingEngineJAX` drives it
    between working-set splices, via :func:`run`'s ``segment=`` mode)."""
    step = _build_step(params, key, **statics)
    Rw = params["t_arr"].shape[0]
    dt = params["t_arr"].dtype
    inf = jnp.inf

    def cond(state):
        c, i = state
        ta = jnp.where(c["aptr"].astype(dt) < params["A"],
                       params["t_arr"][jnp.clip(c["aptr"], 0, Rw - 1)], inf)
        tmin = jnp.minimum(ta, c["t_next"].min())
        return ((tmin <= params["h_eff"]) & (tmin < params["frontier"])
                & (i < budget))

    def body(state):
        c, i = state
        return step(c, i.astype(jnp.uint32)), i + 1

    return jax.lax.while_loop(cond, body, (carry, i0))


def _as_keys(keys):
    """Normalize one-or-many seed specs (ints or PRNG keys) to arrays."""
    if isinstance(keys, (list, tuple)):
        return jnp.stack([jax.random.PRNGKey(int(k))
                          if isinstance(k, (int, np.integer)) else k
                          for k in keys])
    if isinstance(keys, (int, np.integer)):
        return jax.random.PRNGKey(int(keys))
    return keys


def run(params, keys, *, placement: str = "vmap", multi: bool = False,
        segment=None, shard: Optional[dict] = None, **statics):
    """Unified entry for every way this engine executes.

    One facade over the jitted kernels, so callers (the sweep's
    ``engine_jax`` evaluator, ``bench_engine_speed``, the streaming
    engine) never reach into module internals:

    * ``placement="single"``    one replication (``keys`` is one seed or
      PRNG key);
    * ``placement="vmap"``      a replication batch on one device
      (``keys`` is a sequence/stack; the bitwise oracle);
    * ``placement="shard_map"`` the same batch partitioned over the
      devices' 1-D cells mesh (bitwise identical; ``shard`` forwards
      tiling kwargs to :func:`repro.sweep.sharded.run_sharded`);
    * ``multi=True``            vmap/shard the leading *instance* axis of
      ``params`` together with ``keys`` (the ``run_engine_multi``
      semantics: DistServe split scans, lockstep trace sets);
    * ``segment=(carry, i0, budget)``  streamed-replay segment mode:
      continue ``carry`` under the frontier-capped while loop instead of
      a fresh replay (placement must be ``"single"``; ``statics`` then
      exclude ``n_steps``/``loop``).

    ``statics`` are the usual ``_STATICS`` kwargs
    (:attr:`ClusterEngineJAX.statics`).
    """
    keys = _as_keys(keys)
    if segment is not None:
        if placement != "single" or multi:
            raise ValueError("segment mode is single-placement only")
        carry, i0, budget = segment
        return _run_segment(params, keys, carry, i0, budget, **statics)
    if placement == "single":
        if multi:
            raise ValueError("multi needs a batch placement (vmap|shard_map)")
        return run_engine(params, keys, **statics)
    if placement == "vmap":
        return (run_engine_multi if multi
                else run_engine_batch)(params, keys, **statics)
    if placement == "shard_map":
        from repro.sweep.sharded import run_sharded

        st = dict(statics)
        if multi:
            raw, _ = run_sharded(
                lambda _rep, pk: _run_core(pk[0], pk[1], **st),
                None, (params, keys), **(shard or {}))
        else:
            raw, _ = run_sharded(lambda p, k: _run_core(p, k, **st),
                                 params, keys, **(shard or {}))
        return raw
    raise ValueError(f"unknown placement {placement!r} (expected "
                     f"single|vmap|shard_map)")


class ClusterEngineJAX:
    """Batched trace-replay twin of :class:`ClusterEngine`.

    Same classes/policy/:class:`EngineConfig` inputs and the same
    summary-metric keys, but the trace and horizon are fixed at
    construction (they determine the tensor shapes and the static scan
    budget) and replications run as one ``jax.vmap`` batch over PRNG
    keys.  Gate-and-route family, vLLM-, Sarathi- and DistServe-style
    baselines are supported; failures and the online controller are not
    (see the module docstring).

    ``max_steps`` caps the scan budget below the hard bound; the
    ``budget_exhausted`` diagnostic then reports whether the cap
    truncated the replay.  ``max_requests`` caps the tensorized trace
    (``n_dropped`` reports the overflow).  ``k_events`` unrolls the
    multi-event hot path (k consecutive events per loop step with one
    merged (R,)-scatter flush per block -- bitwise identical results,
    see the module docstring); the default 1 keeps the historical
    one-event body.
    """

    def __init__(self, classes: Sequence[WorkloadClass], policy: PolicySpec,
                 cfg: EngineConfig, trace, horizon: float, *,
                 drain: bool = False, max_steps: Optional[int] = None,
                 max_requests: Optional[int] = None, loop: str = "while",
                 k_events: int = 1, fastforward: bool = False,
                 telemetry=None):
        if loop not in ("while", "scan"):
            raise ValueError(f"loop must be while|scan, got {loop!r}")
        if int(k_events) < 1:
            raise ValueError(f"k_events must be >= 1, got {k_events!r}")
        if cfg.record_queues_every > 0:
            raise ValueError("engine_jax does not record queue traces; "
                             "use the Python ClusterEngine")
        self.classes = tuple(classes)
        self.I = len(self.classes)
        self.policy = policy
        self.cfg = cfg
        self.n = int(cfg.n_servers)
        prim = cfg.prim

        tt = (trace if isinstance(trace, TraceTensors)
              else tensorize_trace(trace, max_requests=max_requests))
        self.trace = tt
        if tt.n_real and int(tt.cls[tt.valid].max()) >= self.I:
            raise ValueError(
                f"trace references class {int(tt.cls[tt.valid].max())} but "
                f"only {self.I} classes were given")

        # horizon semantics of ClusterEngine.run: stop at the last prompt
        # arrival unless draining (paper Section 6.2 convention)
        arr_t = tt.t[tt.valid & (tt.t <= horizon)]
        last_arrival = float(arr_t.max()) if arr_t.size else float(horizon)
        self.h_eff = float(horizon) if drain else min(float(horizon),
                                                      last_arrival)
        arrived = tt.valid & (tt.t <= self.h_eff)

        self.budget = iteration_budget(tt, cfg, self.h_eff, arrived=arrived)
        self.n_steps = (self.budget if max_steps is None
                        else min(self.budget, int(max_steps)))

        self.gate_kind = _gate_kind(policy)
        if policy.router not in ("solo_first", "local_fcfs", "immediate",
                                 "randomized"):
            raise ValueError(f"unknown router {policy.router!r}")
        self.router_kind = policy.router
        # fail at construction, not at first trace: _build_step re-checks
        # but only when the jit cache misses
        if fastforward and policy.router not in ("solo_first",
                                                 "local_fcfs"):
            raise ValueError(
                "fastforward needs a deterministic global-buffer router "
                f"(solo_first/local_fcfs), got {policy.router!r}")
        self.partition = "none" if policy.partition == "none" else "static"
        self.M = int(policy.mixed_target(self.n))
        pw_m, pw_s = policy.pool_weights_mixed, policy.pool_weights_solo
        if (pw_m is None) != (pw_s is None):
            raise ValueError("engine_jax needs both pool-weight vectors "
                             "or neither")
        self.has_pw = pw_m is not None

        # per-class FCFS tables: class i's rids in arrival order (a class
        # queue is then a [qhead, qarr) window over its table row)
        class_rids = np.full((self.I, tt.R), tt.R, dtype=np.int32)
        for i in range(self.I):
            rids = np.nonzero(arrived & (tt.cls == i))[0]
            class_rids[i, : rids.size] = rids

        # static routing order: solo servers first for solo_first
        # (dispatch fills servers along this permutation)
        sids = np.arange(self.n, dtype=np.int32)
        if self.router_kind == "solo_first":
            perm_srv = np.concatenate([sids[self.M:], sids[: self.M]])
        else:
            perm_srv = sids

        dt = jnp.result_type(float)
        ones = np.ones(self.I)

        def a(v):
            return jnp.asarray(v, dtype=dt)

        gate = policy.gate
        self.params = {
            "t_arr": a(np.where(arrived, tt.t, np.inf)),
            "cls": jnp.asarray(tt.cls, jnp.int32),
            "P": a(tt.P),
            "D": a(tt.D),
            "patience": a(tt.patience),
            "class_rids": jnp.asarray(class_rids, jnp.int32),
            "A": a(int(arrived.sum())),
            "x_star": a(gate.x_star if isinstance(gate, OccupancyGate)
                        else ones),
            "qp_star": a(gate.qp_star if isinstance(gate, OccupancyGate)
                         else 0 * ones),
            "ratio": a(gate.ratio if isinstance(gate, PriorityRatioGate)
                       else ones),
            "p_solo": a(policy.solo_prob if policy.solo_prob is not None
                        else ones),
            "pw_m": a(pw_m if pw_m is not None else ones),
            "pw_s": a(pw_s if pw_s is not None else ones),
            "c_p": a(cfg.pricing.c_p),
            "c_d": a(cfg.pricing.c_d),
            "alpha": a(prim.alpha),
            "beta": a(prim.beta),
            "tau_solo": a(prim.tau_solo),
            "b_s": a(cfg.solo_kv_slope),
            "kv_xfer": a(0.0),
            "B": a(prim.batch_cap),
            "C": a(prim.chunk),
            "Mi": jnp.asarray(self.M, jnp.int32),
            "perm_srv": jnp.asarray(perm_srv, jnp.int32),
            "n_f": a(self.n),
            "h_eff": a(self.h_eff),
        }
        # plugged iteration-time model (repro.calibration protocol):
        # affine-kind models override the four surface scalars; table-kind
        # models add knot arrays and flip the static interp dispatch.  No
        # model (the default) leaves params and statics byte-identical.
        self.model_kind = "affine"
        m = cfg.iter_model
        if m is not None:
            self.model_kind = getattr(m, "kind", "affine")
            if self.model_kind == "table":
                for k, v in m.knots().items():
                    self.params[k] = a(np.asarray(v))
            elif hasattr(m, "jax_params"):
                for k, v in m.jax_params().items():
                    self.params[k] = a(v)
            else:  # generic protocol model: sample the affine scalars
                self.params["alpha"] = a(m.tau_mix(0.0))
                self.params["beta"] = a(m.tau_mix(1.0) - m.tau_mix(0.0))
                self.params["tau_solo"] = a(m.tau_solo(0.0))
                self.params["b_s"] = a(m.tau_solo(1.0) - m.tau_solo(0.0))
        if cfg.fleet is not None:
            # heterogeneous fleet: the four time surfaces plus the
            # KV-transfer charge become (n,) per-server arrays (B/chunk
            # stay fleet-uniform -- the pointer tables assume one B).
            # The homogeneous path above keeps scalars, so its compiled
            # HLO is byte-identical to the pre-fleet engine.
            if m is not None:
                raise ValueError("EngineConfig.fleet and iter_model are "
                                 "mutually exclusive")
            if int(cfg.fleet.n) != self.n:
                raise ValueError(
                    f"fleet has {int(cfg.fleet.n)} servers but "
                    f"n_servers={self.n}")
            fp = cfg.fleet.server_params(prim)
            for k_ in ("alpha", "beta", "tau_solo", "b_s", "kv_xfer"):
                self.params[k_] = a(fp[k_])
        self._static = dict(
            n_steps=self.n_steps, n=self.n, B=int(prim.batch_cap),
            gate_kind=self.gate_kind, router_kind=self.router_kind,
            charging=policy.charging, partition=self.partition,
            sarathi=bool(cfg.sarathi_budget),
            unchunked=bool(cfg.vllm_unchunked),
            prefill_only=bool(policy.prefill_only_mixed),
            has_pw=self.has_pw,
            # deadline machinery compiles away on the (default) traces
            # where every request has patience == inf
            expiry=bool(np.isfinite(tt.patience[arrived]).any()),
            loop=loop, model_kind=self.model_kind,
            k_events=int(k_events), fastforward=bool(fastforward),
            # hashable ProbeSpec (or None): rides the jit static path,
            # so probes-off compiles the byte-identical bare kernel
            telemetry=resolve_probe_spec(telemetry))
        self.telemetry = self._static["telemetry"]

    # -- raw (device array) interface -------------------------------------
    def _key(self, seed):
        if isinstance(seed, (int, np.integer)):
            return jax.random.PRNGKey(int(seed))
        return seed

    @property
    def statics(self) -> dict:
        """The compile-time kwargs of this instance's kernel -- pass them
        to the module-level :func:`run` facade next to :attr:`params`."""
        return dict(self._static)

    def run_raw(self, seed) -> dict:
        """One replication; returns the raw scan carry (device arrays)."""
        return run(self.params, self._key(seed), placement="single",
                   **self._static)

    def run_batch_raw(self, seeds: Sequence, *, placement: str = "vmap",
                      shard: Optional[dict] = None) -> dict:
        """All replications in one batch; leaves gain a leading
        replication axis.  ``placement``/``shard`` as in :func:`run`."""
        return run(self.params, [self._key(s) for s in seeds],
                   placement=placement, shard=shard, **self._static)

    # -- EngineMetrics.summary() interface ---------------------------------
    def _summary(self, o: dict) -> dict:
        st = np.asarray(o["st"])
        t_first = np.asarray(o["t_first"], dtype=np.float64)
        t_last = np.asarray(o["t_last"], dtype=np.float64)
        t_arr = np.asarray(self.params["t_arr"], dtype=np.float64)
        D = np.asarray(self.params["D"], dtype=np.float64)

        arrivals = int((st != _NOT_ARRIVED).sum())
        completions = int((st == _DONE).sum())
        emitted = np.isfinite(t_first)
        ttft = t_first[emitted] - t_arr[emitted]
        tp_mask = (st == _DONE) & (D > 1)
        tpot = ((t_last[tp_mask] - t_first[tp_mask])
                / np.maximum(D[tp_mask] - 1.0, 1.0))

        def pct(v, q):
            return float(np.percentile(v, q)) if v.size else float("nan")

        # budget diagnostic: an event still pending before the horizon
        # means the step cap cut the replay short
        ap = int(o["aptr"])
        next_arr = (float(t_arr[ap]) if ap < t_arr.shape[0]
                    and st[ap] == _NOT_ARRIVED else np.inf)
        next_t = min(next_arr,
                     float(np.asarray(o["t_next"], dtype=np.float64).min(
                         initial=np.inf)))
        horizon = self.h_eff if self.h_eff > 0 else 1.0
        return {
            "revenue_rate": float(o["rev"]) / horizon,
            "completion_rate": completions / arrivals if arrivals else 0.0,
            "ttft_mean": float(ttft.mean()) if ttft.size else float("nan"),
            "ttft_p95": pct(ttft, 95),
            "ttft_p99": pct(ttft, 99),
            "tpot_mean": float(tpot.mean()) if tpot.size else float("nan"),
            "tpot_p95": pct(tpot, 95),
            "tpot_p99": pct(tpot, 99),
            "completions": completions,
            "arrivals": arrivals,
            "abandons": int(o["abandons"]),
            "t_end": float(o["t"]),
            "budget_exhausted": float(next_t <= self.h_eff),
            "n_iters": float(o["n_iters"]),
            "n_events": float(o["n_events"]),
            "n_steps": float(self.n_steps),
            "n_dropped": float(self.trace.n_dropped),
        }

    def summaries_from_raw(self, raw: dict) -> list:
        """Split a :meth:`run_batch_raw` carry into per-replication
        summary dicts (:meth:`EngineMetrics.summary` keys + engine
        diagnostics)."""
        host = {k: np.asarray(v) for k, v in raw.items()}
        reps = host["t"].shape[0]
        return [self._summary({k: v[r] for k, v in host.items()})
                for r in range(reps)]

    # -- telemetry interface ----------------------------------------------
    def telemetry_from_raw(self, raw: dict) -> dict:
        """Host-side probe report (:func:`repro.telemetry.extract_probes`)
        from a raw carry; batched carries reduce over their leading
        axes.  Requires the engine to have been built with
        ``telemetry=``."""
        if self.telemetry is None:
            raise ValueError("engine was built without telemetry; pass "
                             "telemetry=ProbeSpec(...) (or True)")
        return extract_probes(raw, self.telemetry,
                              horizon=self.h_eff if self.h_eff > 0 else 1.0,
                              n_servers=self.n)

    def lifecycle_records_from_raw(self, raw: dict,
                                   limit: Optional[int] = None) -> list:
        """Per-request lifecycle records for the Chrome-trace exporter
        (:func:`repro.telemetry.lifecycle_events`) from a
        SINGLE-replication raw carry.  The JAX carry tracks
        arrival/first/last only, so queue wait and prefill render as one
        merged span."""
        st = np.asarray(raw["st"])
        if st.ndim != 1:
            raise ValueError("lifecycle records need a single-replication "
                             "carry; index one replication first")
        t_first = np.asarray(raw["t_first"], dtype=np.float64)
        t_last = np.asarray(raw["t_last"], dtype=np.float64)
        t_arr = np.asarray(self.params["t_arr"], dtype=np.float64)
        cls = np.asarray(self.params["cls"])
        names = ("not_arrived", "queued", "prefill", "buffered", "decode",
                 "done", "abandoned")
        records = []
        for rid in np.nonzero(st != _NOT_ARRIVED)[0]:
            records.append({
                "rid": int(rid),
                "cls": self.classes[int(cls[rid])].name,
                "t_arr": float(t_arr[rid]),
                "t_first": float(t_first[rid]),
                "t_last": float(t_last[rid]),
                "state": names[int(st[rid])],
            })
            if limit is not None and len(records) >= limit:
                break
        return records

    def run(self, seed=0) -> dict:
        return self._summary({k: np.asarray(v)
                              for k, v in self.run_raw(seed).items()})

    def run_batch(self, seeds: Sequence, *, placement: str = "vmap",
                  shard: Optional[dict] = None) -> list:
        return self.summaries_from_raw(
            self.run_batch_raw(seeds, placement=placement, shard=shard))
