"""Served driver: a gate-and-route fleet of ``ServerEngine``s under traffic.

Set-up builds the weights from the seed, plans the fleet with
``solve_bundled_lp``, builds ``repro.serving.cluster.RealCluster`` and runs
it for ``warmup_virtual_s`` of virtual time, which compiles every program
the window uses.  The window then drives ``RealCluster.run`` over
consecutive slices of ``slice_virtual_s`` virtual seconds, carrying queues
and slots over, until the wall-clock window has passed.

Spans come from this file: the public calls ``ServerEngine.step``,
``start_prefill``, ``extract_slot`` and ``inject_slot`` are wrapped on each
engine instance.  What a step did (tokens out, decoding slots and their
lengths, the prefill chunk) is read from the engine's host-side slots
around the call, which costs no device round trip.

The check runs once the window has closed and the fleet is freed: every
request finished in the window, and so every server and slot that one of
them decoded in, is read against the float32 reference in
``bench/reference``.
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from bench import harness
from bench.generators import load_generator

__all__ = ["ServedCell", "program_config", "TRACE_SYNC"]

#: each ``bench.step`` span runs exactly one of these programs
TRACE_SYNC = ("bench.step", ("decode_step", "mixed_step"))


def program_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import AttentionConfig, ModelConfig

    return ModelConfig(
        name=c["name"], family="dense",
        n_layers=int(c["num_hidden_layers"]), d_model=int(c["hidden_size"]),
        d_ff=int(c["intermediate_size"]), vocab_size=int(c["vocab_size"]),
        attn=AttentionConfig(
            n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]), rope_theta=float(c["rope_theta"]),
            qkv_bias=bool(c["qkv_bias"])),
        pattern=("attn",), tie_embeddings=bool(c["tie_word_embeddings"]),
        param_dtype=c["torch_dtype"])


class ServedCell:
    def __init__(self, c: dict, mix: dict, seed: int, spans: harness.Spans):
        self.c, self.mix, self.seed, self.spans = c, mix, int(seed), spans
        self.dep = c["deployment"]
        self.ref = importlib.import_module(f"bench.reference.{c['reference']}")
        self.recording = False
        self.steps: list = []        # one tuple per step, see _wrap_step
        self.extract_s: list = []
        self.inject_s: list = []
        self.ttft_s: list = []
        self.prompts: dict = {}
        self.slots: dict = {}        # rid -> {(server, slot)} it decoded in
        self.arrived = 0
        self._pf: dict = {}          # sid -> [rid, wall so far, in window]
        self.vt = 0.0

    # ----------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.core.planning import solve_bundled_lp
        from repro.core.types import Pricing, ServicePrimitives, WorkloadClass
        from repro.serving.cluster import RealCluster

        c, dep, mix = self.c, self.dep, self.mix
        self.params = self.ref.make_params(c, self.seed)
        n = int(dep["servers_here"])
        rate = float(mix["rate_per_s"])
        classes = [WorkloadClass(k["name"], prompt_len=k["mean_prompt"],
                                 decode_len=k["mean_decode"],
                                 arrival_rate=k["share"] * rate / n,
                                 patience=k["patience"])
                   for k in mix["classes"]]
        prim = ServicePrimitives(batch_cap=int(dep["batch_cap"]),
                                 chunk=int(dep["chunk"]))
        self.plan = solve_bundled_lp(classes, prim, Pricing())
        self.cluster = RealCluster(
            program_config(c), self.params, classes, self.plan, prim,
            Pricing(), n_servers=n, max_len=int(dep["max_len"]),
            seed=self.seed)
        for sid, eng in enumerate(self.cluster.engines):
            self._wrap(sid, eng)
        self.traffic = load_generator(mix["generator"]).Traffic(
            mix, self.seed, int(c["vocab_size"]))
        while self.vt < float(dep["warmup_virtual_s"]) - 1e-9:
            self._advance()
        self._sync()

    def _sync(self) -> None:
        import jax

        jax.block_until_ready([e.state for e in self.cluster.engines])

    def _advance(self) -> None:
        dt = float(self.dep["slice_virtual_s"])
        reqs = self.traffic.take(self.vt + dt)
        if self.recording:
            self.arrived += len(reqs)
        self.cluster.run(reqs, horizon=dt)
        self.vt += dt

    # ------------------------------------------------------------ spans
    def _wrap(self, sid: int, eng) -> None:
        step, start = eng.step, eng.start_prefill
        extract, inject = eng.extract_slot, eng.inject_slot

        def wrapped_step():
            pf = eng.prefill
            live = [(i, r, r.tokens_out) for i, r in enumerate(eng.slots)
                    if r is not None]
            dec = [r.prompt_len + t for i, r, t in live
                   if i != eng.prefill_slot]
            done = n = 0
            if pf is not None:
                done = pf[2]
                n = min(eng.chunk, len(pf[1]) - done)
                if done == 0:
                    self._pf[sid] = [pf[0].rid, 0.0, self.recording]
            with self.spans.span("bench.step"):
                t0 = time.perf_counter()
                out = step()
                t1 = time.perf_counter()
            produced = sum(r.tokens_out - t for _, r, t in live)
            first = sum(1 for _, r, t in live if t == 0 and r.tokens_out)
            for i, r, t in live:
                if self.recording and r.tokens_out > t:
                    self.slots.setdefault(r.rid, set()).add((sid, i))
            if pf is not None:
                acc = self._pf[sid]
                acc[1] += t1 - t0
                if first and acc[2] and self.recording:
                    self.ttft_s.append(acc[1])
            if self.recording:
                self.steps.append((sid, pf is not None, t0, t1, len(dec),
                                   sum(dec), produced, first, done, n))
            return out

        def wrapped_start(req, prompt_tokens):
            self.prompts[req.rid] = np.asarray(prompt_tokens)
            with self.spans.span("bench.start_prefill"):
                return start(req, prompt_tokens)

        def wrapped_extract(slot):
            with self.spans.span("bench.extract_slot"):
                t0 = time.perf_counter()
                out = extract(slot)
                t1 = time.perf_counter()
            if self.recording:
                self.extract_s.append(t1 - t0)
            return out

        def wrapped_inject(slot, req, sub, meta):
            with self.spans.span("bench.inject_slot"):
                t0 = time.perf_counter()
                out = inject(slot, req, sub, meta)
                t1 = time.perf_counter()
            if self.recording:
                self.inject_s.append(t1 - t0)
            return out

        eng.step, eng.start_prefill = wrapped_step, wrapped_start
        eng.extract_slot, eng.inject_slot = wrapped_extract, wrapped_inject

    # ----------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        n_done, clipped = len(self.cluster.completed), self.traffic.clipped
        self.recording = True
        with self.spans.span("bench.window"):
            t0 = time.perf_counter()
            deadline = t0 + float(seconds)
            while time.perf_counter() < deadline:
                with self.spans.span("bench.slice"):
                    self._advance()
            self._sync()
            t1 = time.perf_counter()
        self.recording = False
        self.finished = self.cluster.completed[n_done:]
        steps = self.steps
        tok = sum(s[6] for s in steps)
        later = [(s[3] - s[2], s[6] - s[7]) for s in steps if s[6] > s[7]]
        samples = {
            "steps": len(steps), "tokens": tok,
            "tpot_tokens": sum(k for _, k in later),
            "ttft_requests": len(self.ttft_s),
            "handoffs": len(self.inject_s), "arrived": self.arrived,
            "clipped": self.traffic.clipped - clipped,
            "finished": len(self.finished), "virtual_s": self.vt}
        return {"window_s": t1 - t0, "arrived": self.arrived,
                "samples": samples, "tokens": tok, "steps": steps,
                "tpot": later, "ttft_s": list(self.ttft_s),
                "extract_s": list(self.extract_s),
                "inject_s": list(self.inject_s)}

    def release(self) -> None:
        """Free the fleet's device state; the weights stay for the check."""
        for eng in self.cluster.engines:
            eng.state = None
        self.cluster = None
        gc.collect()

    # ------------------------------------------------------------ check
    def sample(self) -> list:
        """The requests the check reads: every one finished in the window."""
        return sorted(self.finished, key=lambda r: r.rid)

    def check(self) -> dict:
        """Numbers compared, each with its limit, and how many failed."""
        c = self.c
        limit = c["correct"]["limit"]
        pick = self.sample()
        seqs, bad = [], 0
        V = int(c["vocab_size"])
        for r in pick:
            toks = r.out_tokens
            prompt = self.prompts[r.rid]
            if (len(toks) != r.decode_len or len(prompt) != r.prompt_len
                    or min(toks) < 0 or max(toks) >= V):
                bad += 1
                continue
            seqs.append((prompt, toks))
        gaps = []
        if seqs:
            gaps = self.ref.max_logit_gap(
                c, self.params, seqs,
                max_len=int(self.dep["max_len"]))["per_sequence"]
        gap = max(gaps, default=float("inf"))
        over = sum(1 for g in gaps if limit is None or not g <= limit)
        used = set().union(*self.slots.values())
        checked = set().union(*(self.slots.get(r.rid, set()) for r in pick))
        return {"numbers": {"max_logit_gap": (gap, limit)},
                "samples": {"requests": len(pick),
                            "tokens": sum(len(t) for _, t in seqs),
                            "slots_used": len(used),
                            "slots_checked": len(checked)},
                "failed": bad + over}


def make(c: dict, mix: dict, seed: int, spans) -> ServedCell:
    return ServedCell(c, mix, seed, spans)
