"""Jit'd public wrapper for the decode-attention kernel.

Pads the cache length to a multiple of the sequence block, so ragged
lengths keep aligned tiles; the padded tail is masked by ``kv_len``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import decode_attention_pallas

__all__ = ["decode_attention"]


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


@partial(jax.jit, static_argnames=("window", "attn_softcap", "block_s",
                                   "interpret"))
def decode_attention(q, k_cache, v_cache, kv_len, *, window=None,
                     k_positions=None, q_positions=None, attn_softcap=None,
                     block_s=256, interpret=False):
    S = k_cache.shape[1]
    bs = min(block_s, _round_up(S, 8))
    pad = _round_up(S, bs) - S
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
        if k_positions is not None:
            k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)),
                                  constant_values=-1)
    return decode_attention_pallas(
        q, k_cache, v_cache, kv_len, window=window, k_positions=k_positions,
        q_positions=q_positions, attn_softcap=attn_softcap, block_s=bs,
        interpret=interpret)
