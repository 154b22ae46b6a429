"""Attention: GQA with RoPE, sliding-window, softcap, prefix-LM; KV caches.

Implementation notes (roofline-driven):

* Prefill/train attention is *blockwise* over query blocks with a **static
  python loop** (unrolled in HLO).  Two reasons: (i) peak memory matches a
  flash-style kernel (no (S,S) score materialisation), and (ii) XLA's
  ``cost_analysis`` counts ``lax.scan`` bodies once, so static unrolling keeps
  the compiled FLOP counts honest (causal blocks also *skip* the strictly
  upper-triangular KV range via static slices -> S^2/2 FLOPs, like a real
  fused kernel).  Only the layer loop is ``lax.scan``-ed (corrected by the
  dry-run's L-extrapolation).
* Decode (Sq == 1) materialises (B, H, S) scores directly (memory-bound,
  matches the decode-attention Pallas kernel's traffic).
* Sliding-window ("local") layers keep a **ring buffer** cache of size
  ``window`` -- this is what makes gemma2/recurrentgemma 500k-decode feasible.

The TPU Pallas kernels in :mod:`repro.kernels` implement the same math; the
XLA path here is the portable oracle and the dry-run lowering target.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import AttentionConfig
from .layers import apply_rope, rope_table, softcap
from .params import PDef

__all__ = [
    "attn_defs",
    "blockwise_attention",
    "decode_attention",
    "attention_prefill",
    "attention_decode",
    "cache_write_decode",
    "init_kv_cache",
]

_NEG = -2.0e9


def attn_defs(cfg: AttentionConfig, d_model: int) -> dict:
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s_in = 1.0 / np.sqrt(d_model)   # fan-in of the (d -> heads) projections
    s_out = 1.0 / np.sqrt(H * D)    # fan-in of the output projection
    defs = {
        "wq": PDef((d_model, H, D), ("embed", "heads", None), scale=s_in),
        "wk": PDef((d_model, KV, D), ("embed", "kv_heads", None), scale=s_in),
        "wv": PDef((d_model, KV, D), ("embed", "kv_heads", None), scale=s_in),
        "wo": PDef((H, D, d_model), ("heads", None, "embed"), scale=s_out),
    }
    if cfg.qkv_bias:
        defs["bq"] = PDef((H, D), ("heads", None), "zeros")
        defs["bk"] = PDef((KV, D), ("kv_heads", None), "zeros")
        defs["bv"] = PDef((KV, D), ("kv_heads", None), "zeros")
    return defs


def _block_mask(q_pos, k_pos, *, causal, window, prefix_len, kv_len,
                slot_idx=None):
    """q_pos (Bq,), k_pos (Bk,) absolute positions -> (B?, Bq, Bk) bool.

    ``slot_idx``: cache slot indices of the keys (differs from k_pos for
    ring caches); ``kv_len`` masks by slot index.  Negative k_pos marks
    empty cache slots.
    """
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    if prefix_len is not None:
        # prefix-LM: bidirectional over the first prefix_len positions
        m = m | (k_pos[None, :] < prefix_len)[None].squeeze(0)
    m &= (k_pos >= 0)[None, :]  # empty ring slots
    if kv_len is not None:
        # kv_len (B,) -> (B, Bq, Bk)
        si = slot_idx if slot_idx is not None else k_pos
        return m[None] & (si[None, None, :] < kv_len[:, None, None])
    return m


def blockwise_attention(
    q, k, v, *,
    q_positions, k_positions,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len=None,
    kv_len=None,
    attn_softcap: Optional[float] = None,
    block_q: int = 512,
):
    """q (B,Sq,H,D); k,v (B,Skv,KV,D) -> (B,Sq,H,D).

    Static python loop over query blocks; causal/local blocks statically slice
    the KV range they can attend to.
    """
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(D)
    block_q = min(block_q, Sq)
    n_blocks = (Sq + block_q - 1) // block_q
    outs = []
    kp = k_positions
    for bi in range(n_blocks):
        s0 = bi * block_q
        s1 = min(Sq, s0 + block_q)
        qb = q[:, s0:s1]
        qp = q_positions[..., s0:s1]
        # static KV range restriction
        lo, hi = 0, Skv
        if causal and Sq == Skv and prefix_len is None and kv_len is None:
            hi = s1
            if window is not None:
                lo = max(0, s0 - (window - 1))
        kb, vb = k[:, lo:hi], v[:, lo:hi]
        kpb = kp[lo:hi]
        # scores: (B, KV, G, Bq, Skv'); bf16 inputs, fp32 accumulation
        qg = qb.reshape(B, s1 - s0, KV, G, D)
        sc = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, kb,
            preferred_element_type=jnp.float32,
        ) * scale
        if attn_softcap is not None:
            sc = softcap(sc, attn_softcap)
        m = _block_mask(
            qp if qp.ndim == 1 else qp[0],
            kpb,
            causal=causal, window=window, prefix_len=prefix_len,
            kv_len=kv_len,
            slot_idx=jnp.arange(lo, hi) if kv_len is not None else None,
        )
        if m.ndim == 2:
            m = m[None, None, None]  # (1,1,1,Bq,Bk)
        else:
            m = m[:, None, None]  # (B,1,1,Bq,Bk)
        sc = jnp.where(m, sc, _NEG)
        p = jax.nn.softmax(sc, axis=-1)
        ob = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), vb)
        outs.append(ob.reshape(B, s1 - s0, H, D))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def decode_attention(q, k_cache, v_cache, k_new, v_new, *, kv_len,
                     k_positions=None, window=None, attn_softcap=None,
                     q_positions=None):
    """Single-token decode: q (B,1,H,D) over the cache (B,S,KV,D), whose
    slots below kv_len (B,) hold earlier tokens, and over the token's own
    k_new, v_new (B,KV,D), which the cache does not hold yet."""
    B, _, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(D)
    qg = q.reshape(B, KV, G, D)
    # Both products contract over the cache's minor axis as it is stored,
    # KV·D, so each layer's K and V are read in place, with no relayout:
    # a head's query fills its KV head's D columns and zeros the rest.
    eye = jnp.eye(KV, dtype=q.dtype)
    q_rows = jnp.einsum("bkgd,kj->bkgjd", qg, eye).reshape(B, KV, G, KV * D)
    sc = jnp.einsum(
        "bkgc,bsc->bkgs", q_rows, k_cache.reshape(B, S, KV * D),
        preferred_element_type=jnp.float32,
    ) * scale
    sn = jnp.einsum(
        "bkgd,bkd->bkg", qg, k_new,
        preferred_element_type=jnp.float32,
    ) * scale
    if attn_softcap is not None:
        sc = softcap(sc, attn_softcap)
        sn = softcap(sn, attn_softcap)
    pos = jnp.arange(S)
    valid = pos[None, :] < kv_len[:, None]  # (B,S)
    if window is not None and k_positions is not None and q_positions is not None:
        # ring cache: entries store absolute positions
        valid &= q_positions[:, None] - k_positions < window
        valid &= k_positions <= q_positions[:, None]
    sc = jnp.where(valid[:, None, None, :], sc, _NEG)
    # softmax over the cache's scores and the token's own
    m = jnp.maximum(sc.max(axis=-1), sn)
    e = jnp.exp(sc - m[..., None])
    en = jnp.exp(sn - m)
    denom = e.sum(axis=-1) + en
    p = (e / denom[..., None]).astype(v_cache.dtype)
    pn = (en / denom).astype(v_cache.dtype)
    o_rows = jnp.einsum(
        "bkgs,bsc->bkgc", p, v_cache.reshape(B, S, KV * D),
        preferred_element_type=jnp.float32,
    ).reshape(B, KV, G, KV, D)
    o = (jnp.einsum("bkgjd,kj->bkgd", o_rows, eye.astype(jnp.float32))
         + jnp.einsum("bkg,bkd->bkgd", pn, v_new,
                      preferred_element_type=jnp.float32))
    return o.astype(v_cache.dtype).reshape(B, 1, H, D)


# ------------------------------------------------------------------ caches


def init_kv_cache(batch, max_len, n_kv, head_dim, dtype, ring_window=None,
                  quant=False):
    """KV cache; ring-buffered when ``ring_window`` is set (local layers).

    K and V are stored as (B, S, KV·D), a token's heads side by side on the
    minor axis, so that writing one token touches one row of each and
    needs no relayout of the cache; readers view them as (B, S, KV, D).

    ``quant=True`` stores K/V in int8 with per-(token, kv-head) fp16 scales
    (~2x less decode HBM traffic than bf16; the scale overhead is
    2/head_dim).  Quantisation happens in the cache writers; readers
    dequantise on load.
    """
    S = min(max_len, ring_window) if ring_window else max_len
    kv_dtype = jnp.int8 if quant else dtype
    cache = {
        "k": jnp.zeros((batch, S, n_kv * head_dim), kv_dtype),
        "v": jnp.zeros((batch, S, n_kv * head_dim), kv_dtype),
        # absolute position of each slot (ring caches need it for masking)
        "pos": jnp.full((batch, S), -1, jnp.int32),
    }
    if quant:
        cache["k_s"] = jnp.zeros((batch, S, n_kv), jnp.float16)
        cache["v_s"] = jnp.zeros((batch, S, n_kv), jnp.float16)
    return cache


def _quantize_kv(x):
    """x (..., D) -> (int8 values, scale over the last axis)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


def _cache_entries(k, v, quant):
    """k, v (..., KV, D) -> the cache's entries for those tokens: K and V
    as (..., KV·D), int8 with (..., KV) scales when ``quant``."""
    flat = lambda a: a.reshape(a.shape[:-2] + (-1,))  # noqa: E731
    if quant:
        qk, sk = _quantize_kv(k)
        qv, sv = _quantize_kv(v)
        return {"k": flat(qk), "v": flat(qv), "k_s": sk, "v_s": sv}
    return {"k": flat(k), "v": flat(v)}


def cache_kv_arrays(entries, dtype, n_kv):
    """Read (k, v) from a cache, or from entries of it, as (..., KV, D),
    dequantised to ``dtype`` if int8-quantised."""
    k, v = (entries[n].reshape(entries[n].shape[:-1] + (n_kv, -1))
            for n in ("k", "v"))
    if "k_s" in entries:
        return (_dequantize_kv(k, entries["k_s"], dtype),
                _dequantize_kv(v, entries["v_s"], dtype))
    return k, v


def cache_write_prefill(cache, k, v, positions):
    """Write a full prefill chunk at positions (B,S) (assumed in range).

    For ring caches only the last `ring` tokens land (modulo write); the
    inputs are sliced first so duplicate ring slots are never scattered.
    """
    S_cache = cache["pos"].shape[1]
    if k.shape[1] > S_cache:
        k = k[:, -S_cache:]
        v = v[:, -S_cache:]
        positions = positions[:, -S_cache:]
    idx = positions % S_cache
    b = jnp.arange(k.shape[0])[:, None]
    new = dict(_cache_entries(k, v, "k_s" in cache), pos=positions)
    return {n: cache[n].at[b, idx].set(a) for n, a in new.items()}


def cache_write_decode(cache, new, positions, active=None):
    """Write one token per slot into a layer-stacked cache, in place.

    ``cache`` leaves are (L, B, S, ...); ``new`` holds the entries that
    :func:`attention_decode` returned, stacked over the layers (L, B,
    ...); positions (B,).  A slot whose ``active`` is False gets an index
    past the cache's end, so its write is dropped and none of its bytes
    change.  With the cache donated, the scatter updates its buffer.
    """
    L, B, S_cache = cache["pos"].shape
    idx = positions % S_cache
    if active is not None:
        idx = jnp.where(active, idx, S_cache)
    # one (layer, slot, index) triple per row written: only the minor axis
    # is a window, so the scatter keeps the cache's layout
    lay = jnp.arange(L)[:, None]
    b = jnp.arange(B)[None, :]
    new = dict(new, pos=jnp.broadcast_to(positions, (L, B)))
    return dict(cache, **{
        n: cache[n].at[lay, b, idx[None, :]].set(a, mode="drop",
                                                  unique_indices=True)
        for n, a in new.items()})


# ------------------------------------------------------------ full blocks


def _project_qkv(cfg: AttentionConfig, p, x):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return q, k, v


def attention_prefill(cfg: AttentionConfig, p, x, positions, *, local: bool,
                      cache=None, prefix_len=None, kernel_impl: str = "xla",
                      continuation: bool = False):
    """Full-sequence attention; optionally writes the cache.

    positions: (B, S) absolute positions.  With ``continuation=True`` the
    chunk is first merged into the cache and queries attend over the whole
    cached context (chunked-prefill semantics; assumes batch rows share the
    chunk layout, which holds for the engine's one-request chunks and the
    dry-run's uniform batches).  Returns (out, new_cache).
    """
    q, k, v = _project_qkv(cfg, p, x)
    if cfg.rope:
        sin, cos = rope_table(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    window = cfg.window if local else None
    new_cache = None
    if cache is not None:
        new_cache = cache_write_prefill(cache, k, v, positions)
    if continuation:
        assert new_cache is not None, "continuation needs a cache"
        kk, vv = cache_kv_arrays(new_cache, v.dtype, cfg.n_kv_heads)
        S_cache = kk.shape[1]
        kv_len = jnp.minimum(positions[:, -1] + 1, S_cache)
        out = blockwise_attention(
            q, kk, vv,
            q_positions=positions[0] if positions.ndim > 1 else positions,
            k_positions=new_cache["pos"][0],
            causal=cfg.causal, window=window, prefix_len=prefix_len,
            kv_len=kv_len, attn_softcap=cfg.attn_softcap,
        )
    elif kernel_impl == "pallas":
        from repro.kernels.prefill_attention import ops as pf_ops

        out = pf_ops.prefill_attention(
            q, k, v, causal=cfg.causal, window=window,
            attn_softcap=cfg.attn_softcap, prefix_len=prefix_len,
        )
    else:
        out = blockwise_attention(
            q, k, v,
            q_positions=positions[0] if positions.ndim > 1 else positions,
            k_positions=positions[0] if positions.ndim > 1 else positions,
            causal=cfg.causal, window=window, prefix_len=prefix_len,
            attn_softcap=cfg.attn_softcap,
        )
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return proj, new_cache


def attention_decode(cfg: AttentionConfig, p, x, positions, cache, *,
                     local: bool):
    """One-token decode; positions (B,) = current index.

    The cache is only read: the token attends over it and over its own K
    and V.  Returns (out, entries), the token's cache entries, which
    :func:`cache_write_decode` writes once the layer loop is done.
    """
    q, k, v = _project_qkv(cfg, p, x)  # (B,1,·,D)
    if cfg.rope:
        sin, cos = rope_table(positions[:, None], cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    new = {n: a.astype(cache[n].dtype) for n, a in
           _cache_entries(k[:, 0], v[:, 0], "k_s" in cache).items()}
    # attend to the token as the cache will hold it
    k_new, v_new = cache_kv_arrays(new, v.dtype, cfg.n_kv_heads)
    kk, vv = cache_kv_arrays(cache, v.dtype, cfg.n_kv_heads)
    kv_len = jnp.minimum(positions, kk.shape[1])
    out = decode_attention(
        q, kk, vv, k_new, v_new, kv_len=kv_len,
        k_positions=cache["pos"], q_positions=positions,
        window=cfg.window if local else None,
        attn_softcap=cfg.attn_softcap,
    )
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return proj, new
