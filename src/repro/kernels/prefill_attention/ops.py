"""Jit'd public wrapper for the prefill flash-attention kernel.

Pads head_dim to the TPU lane width (128) and sequence to the block size.
Interpret mode runs only when the caller asks for it; without a TPU, a
compiled call fails in the Pallas lowering.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import prefill_attention_pallas

__all__ = ["prefill_attention"]


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@partial(jax.jit, static_argnames=("causal", "window", "attn_softcap",
                                   "prefix_len", "block_q", "block_k",
                                   "interpret"))
def prefill_attention(q, k, v, *, causal=True, window=None, attn_softcap=None,
                      prefix_len=None, block_q=128, block_k=128,
                      interpret=False):
    S = q.shape[1]
    block_q = min(block_q, max(8, S))
    block_k = min(block_k, max(8, S))
    q, S0 = _pad_to(q, 1, block_q)
    k, _ = _pad_to(k, 1, block_k)
    v, _ = _pad_to(v, 1, block_k)
    # lane padding for head_dim
    q, D0 = _pad_to(q, 3, 128) if not interpret else (q, q.shape[3])
    if not interpret:
        k, _ = _pad_to(k, 3, 128)
        v, _ = _pad_to(v, 3, 128)
    out = prefill_attention_pallas(
        q, k, v, causal=causal, window=window, attn_softcap=attn_softcap,
        prefix_len=prefix_len, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out[:, :S0, :, :D0]
