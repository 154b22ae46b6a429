"""Streamed trace replay over a compacted working-set window.

:class:`~repro.serving.engine_jax.ClusterEngineJAX` replays one
host-padded ``(R,)`` trace: every per-request array -- arrival times,
lifecycle codes, first/last-emission times, the FCFS ring -- is sized by
the *whole* trace, so the padded tables are the memory ceiling and a
million-request replay would allocate dozens of ``(1e6,)`` arrays per
replication.  :class:`StreamingEngineJAX` removes that ceiling: it
drives the *same* compiled step function over a fixed working set of
``window`` rows, consuming the trace as fixed-shape chunks
(:func:`repro.data.traces.chunk_trace` output, or a
:class:`repro.workloads.batch.ScenarioStream` that samples arrivals
on-device as it goes) and retiring finished requests between chunks.

**Segments and the frontier.**  The replay alternates two jitted
kernels.  ``_compact_splice`` retires rows whose future is decided
(``DONE``/``ABANDONED``: their TTFT/TPOT/completion contributions fold
into scalar accumulators), compacts the survivors to the front of the
window with a stable order-preserving permutation (new row ids stay in
arrival order, which is what keeps per-class FCFS an ``argmin`` and the
queue windows valid), remaps every rid-valued structure (decode slots,
active prefills, the FCFS ring) through the permutation, splices the
next chunk's rows after the survivors, and rebuilds the per-class FCFS
tables.  ``_run_segment`` then runs the engine step under a
``while_loop`` whose guard stops *strictly before the frontier* -- the
first arrival of the next, not-yet-spliced chunk -- so no event that
could interact with unseen arrivals is processed early; the
fast-forward window is capped by the same frontier (see
``params["frontier"]`` in the step builder).  With the frontier at
``+inf`` (final segment) the loop simply drains to the horizon.

**What can stream.**  The deterministic global-buffer routers
(``solo_first`` / ``local_fcfs``), any gate family, ``k_events == 1``,
no deadlines (``patience == inf`` -- expiry retires queued rows lazily,
which the compactor does not model).  The working set must hold every
*unfinished* request at any instant: queued + in-prefill + buffered +
decoding rows plus one chunk of future arrivals.  If a splice would
overflow the window the engine raises (never silently drops load);
pick ``window`` above the workload's peak backlog.  Percentile metrics
(`ttft_p95` etc.) are not computable from streamed scalars and are
reported as ``NaN``; means, completion counts and revenue are exact.

Horizon semantics are *drain*: the replay runs to ``horizon``
(generated streams have no meaningful "last arrival" to stop at).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policies import PolicySpec
from repro.core.types import WorkloadClass
from repro.data.traces import (TraceTensors, TraceValidationError,
                               chunk_trace, tensorize_trace)
from repro.telemetry.probes import extract_probes, hist_edges

from .engine_jax import (ClusterEngineJAX, _init_carry,
                         _DECODE, _DONE, _NOT_ARRIVED, _QUEUED,
                         iteration_budget, run as run_engine_facade)
from .engine_sim import EngineConfig

__all__ = ["StreamingEngineJAX", "TraceChunkSource"]


class TraceChunkSource:
    """``next_chunk()`` over pre-chunked :class:`TraceTensors`.

    Accepts either a sequence of equal-shape chunks (``chunk_trace``
    output) or a raw request list plus ``chunk_size`` (chunked here).
    Verifies all chunks share one padded shape -- the streaming engine
    compiles a single splice kernel for that shape.
    """

    def __init__(self, chunks, chunk_size: Optional[int] = None):
        if chunk_size is not None:
            chunks = chunk_trace(chunks, chunk_size)
        self._chunks = list(chunks)
        shapes = {c.R for c in self._chunks}
        if len(shapes) > 1:
            raise TraceValidationError(
                f"chunks must share one padded shape, got {sorted(shapes)}")
        self._it = iter(self._chunks)

    def next_chunk(self) -> Optional[TraceTensors]:
        return next(self._it, None)


@jax.jit
def _compact_splice(carry, tbl, ch, h_eff, tlm_edges=None):
    """Retire finished rows, compact survivors, splice the next chunk.

    Pure function of the carry, the per-request tables and one chunk;
    returns ``(carry', tbl', seg)`` where ``seg`` holds this splice's
    retired-row metric contributions and diagnostics (host-accumulated
    in float64 -- segment-sized partial sums keep float32 exact).  With
    telemetry on, ``tlm_edges`` (the log-spaced histogram edges) folds
    the retired rows' TTFT/E2E latencies into the carry's ``tlm_ttft``/
    ``tlm_e2e`` histograms before their time marks are evicted --
    residual rows are folded host-side at end of stream.
    """
    c = dict(carry)
    tbl = dict(tbl)
    Rw = tbl["t_arr"].shape[0]
    inf = jnp.inf
    iota = jnp.arange(Rw, dtype=jnp.int32)
    f32 = tbl["t_arr"].dtype

    st = c["st"]
    real = tbl["t_arr"] < inf
    keep = (((st >= _QUEUED) & (st <= _DECODE))
            | ((st == _NOT_ARRIVED) & real))
    ret = real & ~keep  # DONE / ABANDONED: metrics are final

    # retired-row metric contributions (computed before any reshuffle)
    t_first, t_last = c["t_first"], c["t_last"]
    D = tbl["D"]
    emitted = ret & jnp.isfinite(t_first)
    done = ret & (st == _DONE)
    tpm = done & (D > 1.0)
    seg = {
        "ret": jnp.sum(ret.astype(f32)),
        "done": jnp.sum(done.astype(f32)),
        "ttft_sum": jnp.sum(jnp.where(emitted, t_first - tbl["t_arr"], 0.0)),
        "ttft_n": jnp.sum(emitted.astype(f32)),
        "tpot_sum": jnp.sum(jnp.where(
            tpm, (t_last - t_first) / jnp.maximum(D - 1.0, 1.0), 0.0)),
        "tpot_n": jnp.sum(tpm.astype(f32)),
    }
    if tlm_edges is not None:
        # retired rows leave the window now: bucket their latencies
        # while the t_first/t_last marks still align with this t_arr
        hb = jnp.searchsorted(tlm_edges, t_first - tbl["t_arr"])
        c["tlm_ttft"] = c["tlm_ttft"].at[hb].add(emitted.astype(f32))
        hb = jnp.searchsorted(tlm_edges, t_last - tbl["t_arr"])
        c["tlm_e2e"] = c["tlm_e2e"].at[hb].add(done.astype(f32))

    # stable keep-first permutation: unique integer keys, so the result
    # is deterministic and order-preserving without relying on sort
    # stability; new rids stay in arrival order
    order = jnp.argsort(jnp.where(keep, iota, Rw + iota)).astype(jnp.int32)
    n_live = jnp.sum(keep.astype(jnp.int32))
    newpos = jnp.zeros(Rw, jnp.int32).at[order].set(iota)
    newpos = jnp.where(keep, newpos, -1)

    def remap(r):
        return jnp.where(r >= 0, newpos[jnp.clip(r, 0, Rw - 1)], -1)

    c["slot_rid"] = remap(c["slot_rid"])
    c["pf_rid"] = remap(c["pf_rid"])
    # FCFS ring: shift the live window to the front, rids remapped
    RL = c["buf"].shape[0]
    rl = jnp.arange(RL, dtype=jnp.int32)
    rwin = c["buf"][jnp.clip(c["buf_hd"] + rl, 0, RL - 1)]
    in_ring = rl < (c["buf_tl"] - c["buf_hd"])
    c["buf"] = jnp.where(in_ring, remap(rwin), -1)
    c["buf_tl"] = c["buf_tl"] - c["buf_hd"]
    c["buf_hd"] = jnp.zeros((), c["buf_hd"].dtype)

    # arrivals cursor: survivors whose arrival was already consumed are
    # exactly the non-NOT_ARRIVED kept rows, and they form a prefix
    c["aptr"] = jnp.sum((keep & (st != _NOT_ARRIVED))
                        .astype(c["aptr"].dtype))

    # splice the chunk's in-horizon rows after the survivors
    C = ch["t"].shape[0]
    chv = ch["valid"] & (ch["t"] <= h_eff)
    n_new = jnp.sum(chv.astype(jnp.int32))
    seg["n_live"] = n_live
    seg["n_new"] = n_new
    seg["overflow"] = (n_live + n_new) > Rw
    pos = jnp.arange(C, dtype=jnp.int32) + n_live

    def splice(old, newv, pad):
        p = jnp.where(iota < n_live, old[order], pad)
        return p.at[pos].set(jnp.where(chv, newv, pad), mode="drop")

    tbl["t_arr"] = splice(tbl["t_arr"], ch["t"], inf)
    tbl["cls"] = splice(tbl["cls"], ch["cls"], 0)
    tbl["P"] = splice(tbl["P"], ch["P"], 1.0)
    tbl["D"] = splice(tbl["D"], ch["D"], 1.0)
    c["st"] = splice(st, jnp.zeros(C, st.dtype), 0)
    c["t_first"] = splice(t_first, jnp.full(C, inf, f32), inf)
    c["t_last"] = splice(t_last, jnp.full(C, -inf, f32), -inf)
    if "tout" in c:  # non-fastforward carry keeps the (R,) token array
        c["tout"] = splice(c["tout"], jnp.zeros(C, f32), 0.0)

    # queue bookkeeping: per-class FCFS tables over queued + future rows
    # (in new-rid order queued rows precede future ones, so the windows
    # [0, #queued_i) are exactly the live queues)
    st2, t2, cls2 = c["st"], tbl["t_arr"], tbl["cls"]
    qf = (st2 == _QUEUED) | ((st2 == _NOT_ARRIVED) & (t2 < inf))
    I = c["qarr"].shape[0]

    def class_row(i):
        m = qf & (cls2 == i)
        r = jnp.argsort(jnp.where(m, iota, Rw + iota)).astype(jnp.int32)
        return jnp.where(iota < jnp.sum(m.astype(jnp.int32)), r, Rw)

    ci = jnp.arange(I, dtype=jnp.int32)
    tbl["class_rids"] = jax.vmap(class_row)(ci)
    c["qhead"] = jnp.zeros(I, c["qhead"].dtype)
    c["qarr"] = jax.vmap(lambda i: jnp.sum(
        ((st2 == _QUEUED) & (cls2 == i)).astype(c["qarr"].dtype)))(ci)

    tbl["A"] = jnp.sum((t2 < inf).astype(f32))
    ta = jnp.where(c["aptr"].astype(f32) < tbl["A"],
                   t2[jnp.clip(c["aptr"], 0, Rw - 1)], inf)
    c["alive"] = jnp.minimum(ta, c["t_next"].min()) <= h_eff
    return c, tbl, seg


def _run_segment(params, key, carry, i0, budget, **statics):
    """Run engine steps until the frontier, the horizon or the budget.

    Thin alias over the :func:`repro.serving.engine_jax.run` facade's
    ``segment=`` mode -- the frontier-capped while loop itself lives
    next to the step kernel in engine_jax.
    """
    return run_engine_facade(params, key, placement="single",
                             segment=(carry, i0, budget), **statics)


class StreamingEngineJAX:
    """Streamed (chunk-fed) twin of :class:`ClusterEngineJAX`.

    Same classes/policy/config inputs; the trace arrives through
    :meth:`run_stream` as a chunk source instead of being fixed at
    construction.  ``window`` is the working-set size (must exceed the
    workload's peak unfinished-request backlog plus one chunk).
    """

    def __init__(self, classes: Sequence[WorkloadClass], policy: PolicySpec,
                 cfg: EngineConfig, horizon: float, *, window: int = 8192,
                 fastforward: bool = True, telemetry=None):
        # an empty window-shaped trace gives us the full policy/params
        # lowering (and its validations) without duplicating it here
        base = ClusterEngineJAX(classes, policy, cfg,
                                tensorize_trace([], pad_to=int(window)),
                                horizon, drain=True,
                                fastforward=fastforward,
                                telemetry=telemetry)
        if base.router_kind not in ("solo_first", "local_fcfs"):
            raise ValueError(
                "StreamingEngineJAX needs a deterministic global-buffer "
                f"router (solo_first/local_fcfs), got {base.router_kind!r}")
        self._base = base
        self.window = int(window)
        self.h_eff = base.h_eff
        self.classes = base.classes
        self.I = base.I
        self.cfg = cfg
        self._statics = {k: v for k, v in base._static.items()
                         if k not in ("n_steps", "loop")}
        self.telemetry = self._statics["telemetry"]
        self._tlm_edges = (
            jnp.asarray(hist_edges(self.telemetry),
                        base.params["t_arr"].dtype)
            if self.telemetry is not None else None)

    def run_stream(self, source, seed=0,
                   max_steps: Optional[int] = None) -> dict:
        """Replay one stream; returns a summary dict (engine keys plus
        ``requests``/``n_segments``/``window_peak`` diagnostics)."""
        src = (source if hasattr(source, "next_chunk")
               else TraceChunkSource(source))
        base = self._base
        Rw = self.window
        dt = base.params["t_arr"].dtype
        st_ = self._statics
        carry = _init_carry(Rw, base.n, int(base.params["B"]), self.I, dt,
                            st_["router_kind"], st_["has_pw"],
                            st_["expiry"], st_["k_events"],
                            st_["fastforward"], st_["telemetry"])
        # the per-segment push count is bounded by the working set, not
        # the whole trace: give the ring two windows of slack
        W = int(base.params["B"]) + 1
        carry["buf"] = jnp.full(2 * Rw + W, -1, jnp.int32)
        tbl = {
            "t_arr": jnp.full(Rw, jnp.inf, dt),
            "cls": jnp.zeros(Rw, jnp.int32),
            "P": jnp.ones(Rw, dt),
            "D": jnp.ones(Rw, dt),
            "class_rids": jnp.full((self.I, Rw), Rw, jnp.int32),
            "A": jnp.zeros((), dt),
        }
        acc = {k: 0.0 for k in ("ret", "done", "ttft_sum", "ttft_n",
                                "tpot_sum", "tpot_n")}
        key = (jax.random.PRNGKey(int(seed))
               if isinstance(seed, (int, np.integer)) else seed)
        h_eff = jnp.asarray(self.h_eff, dt)
        i = jnp.zeros((), jnp.int32)
        budget = 0
        clock_budget = None
        requests = 0
        n_segments = 0
        window_peak = 0
        occupancy = []  # kept rows right after each splice: backlog trace
        t_seam = -np.inf
        C0 = None
        pending = src.next_chunk()
        while pending is not None:
            ch = pending
            if C0 is None:
                C0 = ch.R
            elif ch.R != C0:
                raise TraceValidationError(
                    f"chunk shape changed mid-stream: {ch.R} != {C0}")
            if ch.n_real:
                t_real = ch.t[ch.valid]
                if t_real[0] < t_seam:
                    raise TraceValidationError(
                        f"stream chunks out of order: chunk starts at "
                        f"t={t_real[0]} before the previous chunk's last "
                        f"arrival t={t_seam}")
                t_seam = float(t_real[-1])
                if np.isfinite(ch.patience[ch.valid]).any():
                    raise ValueError(
                        "StreamingEngineJAX does not support deadlines "
                        "(finite patience) yet; use ClusterEngineJAX")
                if int(ch.cls[ch.valid].max(initial=0)) >= self.I:
                    raise ValueError("chunk references an unknown class")
                arrs = {
                    "t": jnp.asarray(ch.t, dt),
                    "cls": jnp.asarray(ch.cls, jnp.int32),
                    "P": jnp.asarray(ch.P, dt),
                    "D": jnp.asarray(ch.D, dt),
                    "valid": jnp.asarray(ch.valid),
                }
                b = iteration_budget(ch, self.cfg, self.h_eff)
                if clock_budget is None:
                    # the clock bound is global: never let per-chunk
                    # summation exceed arrivals + one clock bound
                    clock_budget = b
                budget += b
                carry, tbl, seg = _compact_splice(carry, tbl, arrs, h_eff,
                                                  self._tlm_edges)
                if bool(seg["overflow"]):
                    tail = occupancy[-5:]
                    trace = (", ".join(
                        f"seg{n_segments - len(tail) + j}={v}"
                        for j, v in enumerate(tail))
                        if tail else "none (overflow on first splice)")
                    raise RuntimeError(
                        f"working-set overflow at t~{t_seam:.0f} (segment "
                        f"{n_segments}): {int(seg['n_live'])} live rows + "
                        f"{int(seg['n_new'])} new > window={Rw}; raise "
                        "`window` (peak unfinished backlog exceeded); "
                        f"occupancy after recent splices: {trace}")
                occupancy.append(int(seg["n_live"]) + int(seg["n_new"]))
                window_peak = max(window_peak, occupancy[-1])
                requests += int(seg["n_new"])
                for k in acc:
                    acc[k] += float(seg[k])
            nxt = src.next_chunk()
            while nxt is not None and nxt.n_real == 0:
                nxt = src.next_chunk()
            frontier = (np.inf if nxt is None
                        else float(nxt.t[nxt.valid][0]))
            params = dict(base.params)
            params.update(tbl)
            params["frontier"] = jnp.asarray(frontier, dt)
            cap = budget if max_steps is None else min(budget, int(max_steps))
            carry, i = _run_segment(params, key, carry,
                                    i, jnp.asarray(cap, jnp.int32), **st_)
            n_segments += 1
            pending = nxt

        # residual working set + accumulators -> summary
        o = {k: np.asarray(v) for k, v in carry.items()}
        t_arr = np.asarray(tbl["t_arr"], np.float64)
        D = np.asarray(tbl["D"], np.float64)
        st = o["st"]
        t_first = o["t_first"].astype(np.float64)
        t_last = o["t_last"].astype(np.float64)
        arrivals = int(acc["ret"]) + int((st != _NOT_ARRIVED).sum())
        completions = int(acc["done"]) + int((st == _DONE).sum())
        emitted = np.isfinite(t_first)
        ttft_sum = acc["ttft_sum"] + float(
            (t_first[emitted] - t_arr[emitted]).sum())
        ttft_n = acc["ttft_n"] + float(emitted.sum())
        tpm = (st == _DONE) & (D > 1)
        tpot_sum = acc["tpot_sum"] + float(
            ((t_last[tpm] - t_first[tpm])
             / np.maximum(D[tpm] - 1.0, 1.0)).sum())
        tpot_n = acc["tpot_n"] + float(tpm.sum())
        ap = int(o["aptr"])
        A = float(np.asarray(tbl["A"]))
        next_arr = float(t_arr[ap]) if ap < A else np.inf
        next_t = min(next_arr, float(o["t_next"].min(initial=np.inf)))
        horizon = self.h_eff if self.h_eff > 0 else 1.0
        nan = float("nan")
        if self.telemetry is not None:
            # rows still in the window never hit a splice fold: bucket
            # their latencies now (same f32 values the splice fold sees)
            edges = np.asarray(self._tlm_edges)
            t32 = np.asarray(tbl["t_arr"])
            for key_, tmark, m in (
                    ("tlm_ttft", o["t_first"], emitted),
                    ("tlm_e2e", o["t_last"], st == _DONE)):
                h = o[key_].astype(np.float64, copy=True)
                np.add.at(h, np.searchsorted(edges, tmark[m] - t32[m]), 1.0)
                o[key_] = h
        telemetry = (extract_probes(o, self.telemetry, horizon=horizon,
                                    n_servers=self._base.n)
                     if self.telemetry is not None else None)
        return {
            "revenue_rate": float(o["rev"]) / horizon,
            "completion_rate": completions / arrivals if arrivals else 0.0,
            "ttft_mean": ttft_sum / ttft_n if ttft_n else nan,
            "ttft_p95": nan,  # not computable from streamed scalars
            "ttft_p99": nan,
            "tpot_mean": tpot_sum / tpot_n if tpot_n else nan,
            "tpot_p95": nan,
            "tpot_p99": nan,
            "completions": completions,
            "arrivals": arrivals,
            "abandons": int(o["abandons"]),
            "t_end": float(o["t"]),
            "budget_exhausted": float(next_t <= self.h_eff),
            "n_iters": float(o["n_iters"]),
            "n_events": float(o["n_events"]),
            "n_loop": float(o["n_loop"]),
            "n_steps": float(np.asarray(i)),
            "n_dropped": 0.0,
            "requests": requests,
            "n_segments": n_segments,
            "window_peak": window_peak,
            "window_occupancy": occupancy,
            **({"telemetry": telemetry} if telemetry is not None else {}),
        }
