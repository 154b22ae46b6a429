"""Jit-compilable serving step functions (the data-plane compute).

Three steps, mirroring the paper's iteration taxonomy (Section 2.2):

* ``prefill_step``  -- full-sequence prefill of a request batch (the
  ``prefill_32k`` dry-run cell).
* ``decode_step``   -- one token for every active slot (solo iteration; the
  ``decode_32k`` / ``long_500k`` cells).
* ``mixed_step``    -- one C-token prefill chunk for a designated slot
  *fused with* one decode token for the other slots: the paper's mixed-mode
  GPU iteration as a single compiled program.

All are pure ``(params, state, inputs) -> (state, outputs)`` functions; the
engine (:mod:`repro.serving.engine`) wraps them with slot management, and
launch/dryrun.py lowers them on the production mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.config import ModelConfig

__all__ = ["make_prefill_step", "make_decode_step", "make_mixed_step",
           "init_server_state", "greedy_sample"]


def greedy_sample(logits):
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)


def init_server_state(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16) -> dict:
    """Slot-structured server state: caches + per-slot bookkeeping."""
    return {
        "caches": M.init_cache(cfg, batch, max_len, dtype),
        "length": jnp.zeros((batch,), jnp.int32),   # tokens in cache
        "last_token": jnp.zeros((batch,), jnp.int32),
        "active": jnp.zeros((batch,), jnp.bool_),   # decoding slots
    }


def make_prefill_step(cfg: ModelConfig, *, kernel_impl: str = "xla",
                      unroll: bool = False, continuation: bool = False):
    """Whole-batch prefill: (params, caches, tokens, positions, stubs).

    ``continuation=True`` gives chunked-prefill semantics (queries attend
    over the cached context) -- the engine's mixed iterations use it.
    """

    def prefill_step(params, caches, tokens, positions, *, last_index=None,
                     enc_frames=None, prefix_embeds=None):
        logits, caches = M.forward_prefill(
            cfg, params, tokens, positions, caches,
            enc_frames=enc_frames, prefix_embeds=prefix_embeds,
            unroll=unroll, kernel_impl=kernel_impl, continuation=continuation,
            last_index=last_index)
        return caches, greedy_sample(logits)

    return prefill_step


def make_decode_step(cfg: ModelConfig, *, unroll: bool = False,
                     masked: bool = True):
    """One decode token for every slot (solo iteration).

    With ``masked=True`` (the engine path) inactive slots still *compute*
    (static shapes) but never mutate their caches -- essential when a mixed
    iteration is concurrently prefilling one of the slots.  Each active
    slot's token is written into the cache in place when ``state`` is
    donated.  The dry-run lowers ``masked=False`` (all slots active), the
    pure decode iteration.
    """

    def decode_step(params, state):
        act = state["active"]
        logits, caches = M.forward_decode(
            cfg, params, state["last_token"][:, None], state["length"],
            state["caches"], active=act if masked else None, unroll=unroll)
        nxt = greedy_sample(logits)
        return {
            "caches": caches,
            "length": state["length"] + act.astype(jnp.int32),
            "last_token": jnp.where(act, nxt, state["last_token"]),
            "active": act,
        }, nxt

    return decode_step


def make_mixed_step(cfg: ModelConfig, chunk: int, *, unroll: bool = False):
    """Fused mixed iteration: prefill ``chunk`` tokens into slot ``p_slot``
    while decoding one token on every *other* active slot.

    The chunk runs at batch=1 on a cache slice of the slot-structured state;
    decode masks out the prefilling slot.  ``n_valid`` counts the chunk's
    real tokens (the rest is padding; default: all).  Returns (state,
    decode_tokens, greedy token after the chunk's last real token).
    """
    pf = make_prefill_step(cfg, unroll=unroll, continuation=True)
    dec = make_decode_step(cfg, unroll=unroll)

    # cache leaves are (layer_rep, B, ...): the slot/batch dim is axis 1
    def slice_slot(tree, slot):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1), tree)

    def write_slot(tree, sub, slot):
        return jax.tree.map(
            lambda a, s: jax.lax.dynamic_update_slice_in_dim(a, s, slot,
                                                             axis=1),
            tree, sub)

    def mixed_step(params, state, p_slot, chunk_tokens, chunk_pos0,
                   n_valid=chunk, *, enc_frames=None, prefix_embeds=None):
        # --- prefill chunk on the designated slot (batch of 1)
        sub_cache = slice_slot(state["caches"], p_slot)
        positions = chunk_pos0 + jnp.arange(chunk)[None, :]
        sub_cache, tok = pf(params, sub_cache, chunk_tokens[None, :],
                            positions, last_index=n_valid - 1,
                            enc_frames=enc_frames,
                            prefix_embeds=prefix_embeds)
        caches = write_slot(state["caches"], sub_cache, p_slot)

        # --- decode everyone else
        mask = jnp.arange(state["active"].shape[0]) != p_slot
        dstate = dict(state, caches=caches,
                      active=state["active"] & mask)
        dstate, dec_tokens = dec(params, dstate)
        # restore the prefilling slot's activity bit
        new_state = dict(
            dstate,
            active=jnp.where(mask, dstate["active"], state["active"]),
        )
        return new_state, dec_tokens, tok[0]

    return mixed_step
