"""Decode (single-token) attention kernel for TPU.

The decode iteration is the paper's memory-bound phase: per new token the
whole KV cache streams HBM -> VMEM once.  The kernel tiles the cache
sequence into (block_s, D) VMEM blocks on a (B, KV, n_s_blocks) grid with
the sequence axis innermost-sequential, carrying the online-softmax state
(m, l, acc) for all G group-query heads at once -- one cache stream serves
the whole GQA group, which is the arithmetic-intensity win of GQA decode.

Instead of CUDA-style paged KV (pointer chasing), the cache is a
*contiguous ring* and validity is a per-batch ``kv_len`` scalar plus an
optional absolute-position block (sliding-window archs): dense sequential
DMA, mask in VREGs -- the TPU-native translation of PagedAttention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_pallas"]

_NEG = -2.0e9


def _kernel(kvlen_ref, qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
            m_ref, l_ref, acc_ref, *,
            scale, softcap, window, block_s, n_s_blocks):
    b = pl.program_id(0)
    isb = pl.program_id(2)

    @pl.when(isb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kvlen_ref[b]
    s0 = isb * block_s

    @pl.when(s0 < kv_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)        # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)        # (block_s, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, block_s)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        spos = s0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
        valid = spos < kv_len
        if window is not None:
            qp = qpos_ref[b]
            kp = kpos_ref[0]                       # (1, block_s) ring positions
            valid &= qp - kp < window
            valid &= kp <= qp
        s = jnp.where(valid, s, _NEG)

        m_prev = m_ref[...]                        # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(isb == n_s_blocks - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, kv_len, *, window=None,
                            k_positions=None, q_positions=None,
                            attn_softcap=None, block_s=256,
                            interpret=False):
    """q (B,1,H,D); caches (B,S,KV,D); kv_len (B,) -> (B,1,H,D).

    ``block_s`` must divide S (:func:`repro.kernels.decode_attention.ops.
    decode_attention` pads S to a block multiple).  ``kv_len`` and the
    query positions ride in SMEM as scalar-prefetch operands.
    """
    B, _, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    ns = S // block_s
    scale = 1.0 / (D ** 0.5)

    qg = q.reshape(B, KV, G, D)
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, KV, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    if k_positions is None:
        k_positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                       (B, S))
    if q_positions is None:
        q_positions = jnp.maximum(kv_len - 1, 0).astype(jnp.int32)
    # (B, 1, S): a (1, block_s) tile per step satisfies the TPU tiling rule
    kpos = k_positions.astype(jnp.int32).reshape(B, 1, S)

    kernel = functools.partial(
        _kernel, scale=scale, softcap=attn_softcap, window=window,
        block_s=block_s, n_s_blocks=ns)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                 # kv_len, q_pos
            grid=(B, KV, ns),
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, s, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_s, D),
                             lambda b, h, s, *_: (b, h, s, 0)),
                pl.BlockSpec((1, 1, block_s, D),
                             lambda b, h, s, *_: (b, h, s, 0)),
                pl.BlockSpec((1, 1, block_s), lambda b, h, s, *_: (b, 0, s)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, s, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),   # running max m
                pltpu.VMEM((G, 1), jnp.float32),   # normalizer l
                pltpu.VMEM((G, D), jnp.float32),   # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q_positions.astype(jnp.int32),
      qg, kt, vt, kpos)
    return out.reshape(B, 1, H, D)
