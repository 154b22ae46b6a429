"""Real-compute logical server: B decode slots over a jitted model replica.

This is the data plane behind ``examples/serve_cluster.py`` and
``launch/serve.py``: actual ``forward_prefill`` / ``forward_decode`` compute
(compiled once per mode), slot-structured KV caches, chunked prefill fused
with decode (the paper's mixed iteration), and **KV extraction/injection**
for cross-server decode routing (the real cost behind the paper's "virtual
decode buffer" abstraction).

Iteration *times* on CPU are not meaningful for TPU planning, so the engine
reports calibrated iteration times from ServicePrimitives alongside the real
token outputs -- exactly the paper's split between GPU physics (calibrated
tau) and scheduling semantics.

While a JAX profiler session records, each step, extract and inject leaves
``serve.engine.*`` and ``serve.kv.*`` spans on the profiler's clock
(:mod:`repro.telemetry.spans`), counting ``host_reads`` (device-to-host
reads) and ``eager_ops`` (jnp operations dispatched outside the jitted
programs); with none recording, each span costs one check.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import ServicePrimitives
from repro.models.config import ModelConfig
from repro.telemetry import spans

from .steps import init_server_state, make_decode_step, make_mixed_step

__all__ = ["SlotRequest", "ServerEngine", "server_programs"]


@dataclass
class SlotRequest:
    """Host-side view of a request occupying a slot."""

    rid: int
    cls: int
    prompt_len: int
    decode_len: int  # target output tokens (trace-known, as in the paper)
    tokens_out: int = 0
    out_tokens: list = field(default_factory=list)


def server_programs(cfg: ModelConfig, chunk: int) -> tuple:
    """The jitted ``(decode_step, mixed_step)`` pair a server runs.

    Servers of one model and chunk size share one pair, so a cluster of
    N servers compiles each program once.  Both take the server state
    donated: each step writes its tokens into the state's KV cache in
    place, and the state passed in is consumed.
    """
    return (jax.jit(make_decode_step(cfg), donate_argnums=1),
            jax.jit(make_mixed_step(cfg, chunk), donate_argnums=1))


class ServerEngine:
    def __init__(self, cfg: ModelConfig, params, *, prim: ServicePrimitives,
                 max_len: int, seed: int = 0, programs=None, server: int = 0):
        """The KV cache is held in ``cfg.param_dtype``; ``programs`` is a
        :func:`server_programs` pair to share; ``server`` names the engine
        in its spans."""
        self.server = server
        self.cfg = cfg
        self.params = params
        self.prim = prim
        self.B = prim.batch_cap
        self.chunk = prim.chunk
        self.max_len = max_len
        self.state = init_server_state(cfg, self.B, max_len,
                                       jnp.dtype(cfg.param_dtype))
        self._decode, self._mixed = (programs if programs is not None
                                     else server_programs(cfg, self.chunk))
        self.slots: list[Optional[SlotRequest]] = [None] * self.B
        # host-side prefill progress (one prefill at a time, paper Section 2)
        self.prefill: Optional[tuple[SlotRequest, np.ndarray, int]] = None
        self.prefill_slot: int = -1
        self.rng = np.random.default_rng(seed)

    # ------------------------------------------------------------- capacity
    def free_slots(self) -> list[int]:
        reserved = {self.prefill_slot} if self.prefill else set()
        return [i for i, s in enumerate(self.slots)
                if s is None and i not in reserved]

    @property
    def has_prefill(self) -> bool:
        return self.prefill is not None

    @property
    def n_decoding(self) -> int:
        return sum(
            1 for i, s in enumerate(self.slots)
            if s is not None and i != self.prefill_slot)

    # ------------------------------------------------------------- control
    def start_prefill(self, req: SlotRequest, prompt_tokens: np.ndarray):
        assert self.prefill is None, "one prefill per server"
        free = self.free_slots()
        assert free, "no slot for prefill"
        self.prefill_slot = free[0]
        self.prefill = (req, np.asarray(prompt_tokens, np.int32), 0)
        self.slots[self.prefill_slot] = req

    def extract_slot(self, slot: int):
        """Pull a slot's KV/state out (host trees) for migration.

        Its span carries ``bytes``, the host tree's size, and
        ``live_bytes``, the part of it the slot's length fills."""
        req = self.slots[slot]
        with spans.span("serve.engine.extract", server=self.server,
                        rid=req.rid) as args:
            with spans.span("serve.kv.to_host"):
                sub = jax.tree.map(lambda a: np.asarray(a[:, slot:slot + 1]),
                                   self.state["caches"])
                n = len(jax.tree.leaves(sub))
                spans.add("eager_ops", n)
                spans.add("host_reads", n)
            meta = {
                "length": int(self.state["length"][slot]),
                "last_token": int(self.state["last_token"][slot]),
            }
            # clear the slot
            self.state["length"] = self.state["length"].at[slot].set(0)
            self.state["active"] = self.state["active"].at[slot].set(False)
            spans.add("eager_ops", 4)
            spans.add("host_reads", 2)
            self.slots[slot] = None
            if args is not None:
                size = sum(a.nbytes for a in jax.tree.leaves(sub))
                args["bytes"] = size
                # every cache leaf holds one entry per position
                args["live_bytes"] = size * meta["length"] // self.max_len
        return req, sub, meta

    def inject_slot(self, slot: int, req: SlotRequest, sub, meta):
        """Install a migrated (or freshly prefilled) KV into a local slot."""
        assert self.slots[slot] is None

        def put(a, s):
            return jax.lax.dynamic_update_slice_in_dim(
                a, jnp.asarray(s, a.dtype), slot, axis=1)

        with spans.span("serve.engine.inject", server=self.server,
                        rid=req.rid) as args:
            if args is not None:
                args["bytes"] = sum(a.nbytes for a in jax.tree.leaves(sub))
            with spans.span("serve.kv.to_device"):
                self.state["caches"] = jax.tree.map(put, self.state["caches"],
                                                    sub)
                spans.add("eager_ops", 2 * len(jax.tree.leaves(sub)))
            self.state["length"] = self.state["length"].at[slot].set(
                meta["length"])
            self.state["last_token"] = self.state["last_token"].at[slot].set(
                meta["last_token"])
            self.state["active"] = self.state["active"].at[slot].set(True)
            spans.add("eager_ops", 3)
            self.slots[slot] = req

    def activate_slot(self, slot: int):
        """Begin decoding a slot that was prefilled locally."""
        self.state["active"] = self.state["active"].at[slot].set(True)

    # ----------------------------------------------------------- iteration
    def step(self) -> dict:
        """Run one iteration (mixed if a prefill is staged, else solo).

        Returns {"tau": calibrated seconds, "completed": [SlotRequest],
        "prefill_done": SlotRequest | None, "prefill_slot": int}.

        Its span splits into ``launch`` (inputs to the device, the
        program's call and, on a mixed step, the slot's length fix queued
        behind it), ``fetch`` (the first blocking read of the program's
        output) and ``account`` (everything after).
        """
        out = {"tau": 0.0, "completed": [], "prefill_done": None,
               "prefill_slot": -1}
        with spans.span("serve.engine.step", server=self.server) as args:
            if args is not None:
                args["kind"] = "solo" if self.prefill is None else "mixed"
                args["slots"] = self.n_decoding
                if self.prefill is not None:
                    args["rid"] = self.prefill[0].rid
            if self.prefill is not None:
                self._mixed_iteration(out)
            else:
                with spans.span("serve.engine.launch"):
                    self.state, dec_tokens = self._decode(self.params,
                                                          self.state)
                toks = self._fetch(dec_tokens)
                with spans.span("serve.engine.account"):
                    out["tau"] = self.prim.tau_solo
                    self._account_decode(toks, skip=-1, out=out)
        return out

    def _mixed_iteration(self, out: dict) -> None:
        req, toks, done = self.prefill
        with spans.span("serve.engine.launch"):
            n = min(self.chunk, len(toks) - done)
            chunk = np.zeros((self.chunk,), np.int32)
            chunk[:n] = toks[done:done + n]
            self.state, dec_tokens, first = self._mixed(
                self.params, self.state, self.prefill_slot,
                jnp.asarray(chunk), jnp.full((1, 1), done, jnp.int32), n)
            # fix the slot's length to true progress (chunk may be padded),
            # dispatched while the program runs
            slot = self.prefill_slot
            self.state["length"] = self.state["length"].at[slot].set(
                done + n)
            spans.add("eager_ops", 3)
        dec = self._fetch(dec_tokens)
        with spans.span("serve.engine.account"):
            out["tau"] = self.prim.alpha + self.prim.beta * n
            self._account_decode(dec, skip=slot, out=out)
            if done + n >= len(toks):
                # the prompt's last logits give the first output token,
                # which the slot's first decode step then consumes
                first = int(first)
                self.state["last_token"] = self.state["last_token"].at[
                    slot].set(first)
                spans.add("host_reads")
                spans.add("eager_ops")
                req.tokens_out += 1
                req.out_tokens.append(first)
                if req.tokens_out >= req.decode_len:
                    out["completed"].append(req)
                    self.state["length"] = self.state["length"].at[
                        slot].set(0)
                    spans.add("eager_ops")
                    self.slots[slot] = None
                out["prefill_done"] = req
                out["prefill_slot"] = slot
                self.prefill = None
                self.prefill_slot = -1
            else:
                self.prefill = (req, toks, done + n)

    @staticmethod
    def _fetch(dec_tokens) -> np.ndarray:
        with spans.span("serve.engine.fetch"):
            spans.add("host_reads")
            return np.asarray(dec_tokens)

    def _account_decode(self, toks: np.ndarray, *, skip: int, out: dict):
        reads = done = 0
        for i, req in enumerate(self.slots):
            if req is None or i == skip or i == self.prefill_slot:
                continue
            reads += 1
            if not bool(self.state["active"][i]):
                continue
            req.tokens_out += 1
            req.out_tokens.append(int(toks[i]))
            if req.tokens_out >= req.decode_len:
                done += 1
                out["completed"].append(req)
                self.state["active"] = self.state["active"].at[i].set(False)
                self.state["length"] = self.state["length"].at[i].set(0)
                self.slots[i] = None
        spans.add("host_reads", reads)
        spans.add("eager_ops", reads + 2 * done)
