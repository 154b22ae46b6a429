"""Multi-head Latent Attention (DeepSeek-V2/V3).

The KV cache stores only the compressed latent c_kv (rank r) plus the shared
RoPE key -- (r + d_rope) per token per layer instead of 2*KV*D.  Decode uses
the *absorbed* formulation: queries are projected into latent space
(q_nope @ W_uk) so scores are taken directly against the latent cache, and the
attention output stays in latent space until the per-head W_uv/W_o projection.
This is the memory-roofline win that makes deepseek-v3 decode cheap.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .config import MLAConfig
from .layers import apply_rope, rope_table
from .params import PDef

__all__ = ["mla_defs", "mla_prefill", "mla_decode", "init_mla_cache"]


def mla_defs(cfg: MLAConfig, d_model: int) -> dict:
    H = cfg.n_heads
    s_q = 1.0 / np.sqrt(cfg.q_lora_rank)
    s_kv = 1.0 / np.sqrt(cfg.kv_lora_rank)
    s_o = 1.0 / np.sqrt(H * cfg.v_head_dim)
    return {
        "w_dq": PDef((d_model, cfg.q_lora_rank), ("embed", "q_lora")),
        "w_uq": PDef(
            (cfg.q_lora_rank, H, cfg.qk_nope_dim + cfg.qk_rope_dim),
            ("q_lora", "heads", None), scale=s_q,
        ),
        "w_dkv": PDef((d_model, cfg.kv_lora_rank), ("embed", "kv_lora")),
        "w_kr": PDef((d_model, cfg.qk_rope_dim), ("embed", None)),
        "w_uk": PDef(
            (cfg.kv_lora_rank, H, cfg.qk_nope_dim), ("kv_lora", "heads", None),
            scale=s_kv,
        ),
        "w_uv": PDef(
            (cfg.kv_lora_rank, H, cfg.v_head_dim), ("kv_lora", "heads", None),
            scale=s_kv,
        ),
        "wo": PDef((H, cfg.v_head_dim, d_model), ("heads", None, "embed"),
                   scale=s_o),
    }


def init_mla_cache(cfg: MLAConfig, batch: int, max_len: int, dtype,
                   quant=False):
    """Latent cache; ``quant=True`` stores int8 latents + per-token scales
    (the latent is already compressed -- int8 halves it again)."""
    cache = {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank),
                          jnp.int8 if quant else dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim),
                            jnp.int8 if quant else dtype),
    }
    if quant:
        cache["c_s"] = jnp.zeros((batch, max_len), jnp.float16)
        cache["r_s"] = jnp.zeros((batch, max_len), jnp.float16)
    return cache


def _mla_q(x):
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _mla_write(cache, b, pos2d, c_kv, k_rope):
    """Write latents at (b, pos2d); positions past the end are dropped."""
    if "c_s" in cache:
        qc, sc = _mla_q(c_kv)
        qr, sr = _mla_q(k_rope)
        new = {"c_kv": qc, "k_rope": qr, "c_s": sc, "r_s": sr}
    else:
        new = {"c_kv": c_kv, "k_rope": k_rope}
    return {n: cache[n].at[b, pos2d].set(a, mode="drop")
            for n, a in new.items()}


def _mla_read(cache, dtype):
    if "c_s" in cache:
        c = (cache["c_kv"].astype(jnp.float32)
             * cache["c_s"].astype(jnp.float32)[..., None]).astype(dtype)
        r = (cache["k_rope"].astype(jnp.float32)
             * cache["r_s"].astype(jnp.float32)[..., None]).astype(dtype)
        return c, r
    return cache["c_kv"], cache["k_rope"]


def _queries(cfg: MLAConfig, p, x, positions):
    q = jnp.einsum("bsd,dr->bsr", x, p["w_dq"].astype(x.dtype))
    q = jnp.einsum("bsr,rhk->bshk", q, p["w_uq"].astype(x.dtype))
    q_nope = q[..., : cfg.qk_nope_dim]
    q_rope = q[..., cfg.qk_nope_dim :]
    sin, cos = rope_table(positions, cfg.qk_rope_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, sin, cos)
    return q_nope, q_rope


def mla_prefill(cfg: MLAConfig, p, x, positions, cache=None, block_q=512,
                continuation=False):
    """Full-sequence MLA (causal); writes latent cache.

    ``continuation=True``: chunked-prefill semantics -- the chunk's latents
    are merged into the cache first and queries attend over the cached
    context (absolute positions assumed uniform across batch rows).
    """
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _queries(cfg, p, x, positions)
    c_kv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(x.dtype))
    k_rope = jnp.einsum("bsd,dk->bsk", x, p["w_kr"].astype(x.dtype))
    sin, cos = rope_table(positions, cfg.qk_rope_dim, cfg.rope_theta)
    k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)[:, :, 0, :]

    new_cache = None
    if cache is not None:
        b = jnp.arange(B)[:, None]
        pos2d = positions if positions.ndim > 1 else \
            positions[None, :].repeat(B, 0)
        new_cache = _mla_write(cache, b, pos2d, c_kv, k_rope)

    # absorbed scores: q_lat = q_nope @ W_uk  -> (B,S,H,r)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"].astype(x.dtype))
    scale = 1.0 / np.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if continuation:
        assert new_cache is not None, "continuation needs a cache"
        ckv_all, krope_all = _mla_read(new_cache, x.dtype)
        S_cache = ckv_all.shape[1]
        qpos_abs = positions[0] if positions.ndim > 1 else positions
        sc = (
            jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv_all,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqhk,bsk->bhqs", q_rope, krope_all,
                         preferred_element_type=jnp.float32)
        ) * scale
        kpos = jnp.arange(S_cache)
        sc = jnp.where(kpos[None, None, None, :]
                       <= qpos_abs[None, None, :, None], sc, -2.0e9)
        pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqs,bsr->bqhr", pr, ckv_all)
    else:
        outs = []
        block_q = min(block_q, S)
        n_blocks = (S + block_q - 1) // block_q
        for bi in range(n_blocks):
            s0, s1 = bi * block_q, min(S, (bi + 1) * block_q)
            hi = s1  # causal static restriction
            sc = (
                jnp.einsum("bqhr,bsr->bhqs", q_lat[:, s0:s1], c_kv[:, :hi],
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bqhk,bsk->bhqs", q_rope[:, s0:s1],
                             k_rope[:, :hi],
                             preferred_element_type=jnp.float32)
            ) * scale
            qpos = jnp.arange(s0, s1)
            kpos = jnp.arange(hi)
            sc = jnp.where(
                kpos[None, None, None, :] <= qpos[None, None, :, None],
                sc, -2.0e9)
            pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
            ctx = jnp.einsum("bhqs,bsr->bqhr", pr, c_kv[:, :hi])
            outs.append(ctx)
        ctx = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    o = jnp.einsum("bqhr,rhv->bqhv", ctx, p["w_uv"].astype(x.dtype))
    out = jnp.einsum("bqhv,hvd->bqd", o, p["wo"].astype(x.dtype))
    return out, new_cache


def mla_decode(cfg: MLAConfig, p, x, positions, cache, active=None):
    """One-token absorbed decode over the latent cache; positions (B,).

    A slot whose ``active`` is False writes nothing: its index lies past
    the cache's end and the write is dropped."""
    B = x.shape[0]
    q_nope, q_rope = _queries(cfg, p, x, positions[:, None])
    c_new = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(x.dtype))
    k_new = jnp.einsum("bsd,dk->bsk", x, p["w_kr"].astype(x.dtype))
    sin, cos = rope_table(positions[:, None], cfg.qk_rope_dim, cfg.rope_theta)
    k_new = apply_rope(k_new[:, :, None, :], sin, cos)[:, :, 0, :]
    b = jnp.arange(B)[:, None]
    S = cache["c_kv"].shape[1]
    write = positions if active is None else jnp.where(active, positions, S)
    cache = _mla_write(cache, b, write[:, None], c_new, k_new)
    ckv_all, krope_all = _mla_read(cache, x.dtype)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"].astype(x.dtype))[:, 0]
    scale = 1.0 / np.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    sc = (
        jnp.einsum("bhr,bsr->bhs", q_lat, ckv_all,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bhk,bsk->bhs", q_rope[:, 0], krope_all,
                     preferred_element_type=jnp.float32)
    ) * scale
    valid = jnp.arange(S)[None, :] <= positions[:, None]
    sc = jnp.where(valid[:, None, :], sc, -2.0e9)
    pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhs,bsr->bhr", pr, ckv_all)
    o = jnp.einsum("bhr,rhv->bhv", ctx, p["w_uv"].astype(x.dtype))
    out = jnp.einsum("bhv,hvd->bd", o, p["wo"].astype(x.dtype))[:, None, :]
    return out, cache
