"""Unified model: composes the mixer/channel modules into a full LM.

A model is ``embed -> [segments of layers] -> final_norm -> unembed``.
``segment_layers`` compresses the per-layer BlockSpec list into
``(superblock, repeat)`` segments; each segment's parameters are stacked with
a leading ``repeat`` dim and the forward pass ``lax.scan``s over it (small
HLO, honest memory picture).  For dry-run FLOP accounting the same forward
can be built with ``unroll=True`` (static python loop) so XLA's
``cost_analysis`` sees every layer.

Three entry points, matching the serving/training split of the paper:

* :func:`forward_train`  -- teacher-forced logits over a full sequence.
* :func:`forward_prefill` -- full/chunked prefill that writes caches and
  returns the last-position logits.
* :func:`forward_decode` -- one-token decode step over the caches.

Encoder-decoder (whisper) runs its encoder over stub frame embeddings and
feeds cross-attention KV to every decoder block; prefix-LM (paligemma)
prepends stub patch embeddings with a bidirectional prefix mask.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .attention import (
    attn_defs,
    attention_decode,
    attention_prefill,
    blockwise_attention,
    cache_write_decode,
    init_kv_cache,
)
from .config import BlockSpec, ModelConfig, segment_layers
from .layers import apply_mlp, layernorm, mlp_defs, rmsnorm, softcap
from .mla import init_mla_cache, mla_decode, mla_defs, mla_prefill
from .moe import apply_moe, moe_defs
from .params import PDef, init_params
from .rglru import init_rglru_cache, rglru_decode, rglru_defs, rglru_forward
from .ssm import init_ssm_cache, ssm_decode, ssm_defs, ssm_forward

__all__ = [
    "model_defs",
    "init_cache",
    "forward_train",
    "forward_prefill",
    "forward_decode",
    "greedy_generate",
    "loss_fn",
    "encoder_forward",
    "param_count",
]


# ------------------------------------------------------------------ norms


def _norm_defs(cfg: ModelConfig, d: int) -> dict:
    if cfg.norm == "layernorm":
        return {
            "scale": PDef((d,), ("embed",), "ones"),
            "bias": PDef((d,), ("embed",), "zeros"),
        }
    return {"scale": PDef((d,), ("embed",), "zeros")}  # rmsnorm (1 + scale)


def _apply_norm(cfg: ModelConfig, p: dict, x):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


# ------------------------------------------------------------- block defs


def _block_defs(cfg: ModelConfig, spec: BlockSpec) -> dict:
    d = cfg.d_model
    defs: dict = {"ln1": _norm_defs(cfg, d)}
    if spec.mixer in ("attn", "attn_local"):
        defs["attn"] = attn_defs(cfg.attn, d)
    elif spec.mixer == "mla":
        defs["mla"] = mla_defs(cfg.mla, d)
    elif spec.mixer == "ssm":
        defs["ssm"] = ssm_defs(cfg.ssm, d)
    elif spec.mixer == "rec":
        defs["rec"] = rglru_defs(cfg.rglru, d)
    else:
        raise ValueError(spec.mixer)
    if spec.cross_attn:
        defs["lnx"] = _norm_defs(cfg, d)
        defs["xattn"] = attn_defs(cfg.attn, d)
    if spec.channel == "mlp":
        defs["ln2"] = _norm_defs(cfg, d)
        defs["mlp"] = mlp_defs(d, cfg.d_ff, cfg.mlp_act)
    elif spec.channel == "moe":
        defs["ln2"] = _norm_defs(cfg, d)
        defs["moe"] = moe_defs(cfg.moe, d)
    return defs


def _stack_defs(defs: dict, rep: int) -> dict:
    out = {}
    for k, v in defs.items():
        out[k] = _stack_defs(v, rep) if isinstance(v, dict) else v.stacked(rep)
    return out


def model_defs(cfg: ModelConfig) -> dict:
    """Full parameter-definition tree (PDef leaves)."""
    d, V = cfg.d_model, cfg.vocab_size
    defs: dict = {
        "embed": PDef((V, d), ("vocab", "embed"), scale=0.02),
        "final_norm": _norm_defs(cfg, d),
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = PDef((d, V), ("embed", "vocab"))
    if cfg.attn is not None and not cfg.attn.rope:
        # learned decoder positions (whisper-style)
        defs["pos_embed"] = PDef((cfg.max_seq_len, d), (None, "embed"),
                                 scale=0.02)
    segs = segment_layers(cfg.block_specs())
    for si, (block, rep) in enumerate(segs):
        seg = {}
        for bi, spec in enumerate(block):
            seg[f"b{bi}"] = _stack_defs(_block_defs(cfg, spec), rep)
        defs[f"seg{si}"] = seg
    if cfg.encoder is not None:
        e = cfg.encoder
        enc_block = {
            "ln1": _norm_defs(cfg, e.d_model),
            "attn": attn_defs(cfg.attn.__class__(
                n_heads=e.n_heads, n_kv_heads=e.n_heads,
                head_dim=e.d_model // e.n_heads, rope=False, causal=False,
            ), e.d_model),
            "ln2": _norm_defs(cfg, e.d_model),
            "mlp": mlp_defs(e.d_model, e.d_ff, "gelu"),
        }
        defs["encoder"] = {
            "pos": PDef((e.n_frames, e.d_model), ("frames", "embed"),
                        scale=0.02),
            "layers": _stack_defs(enc_block, e.n_layers),
            "final_norm": _norm_defs(cfg, e.d_model),
        }
    if cfg.mtp:
        defs["mtp"] = {
            "norm": _norm_defs(cfg, d),
            "proj": PDef((2 * d, d), ("ff", "embed")),
        }
    return defs


# ------------------------------------------------------------------ caches


def _block_cache(cfg: ModelConfig, spec: BlockSpec, batch: int, max_len: int,
                 dtype):
    if spec.mixer in ("attn", "attn_local"):
        ring = cfg.attn.window if spec.mixer == "attn_local" else None
        c = init_kv_cache(batch, max_len, cfg.attn.n_kv_heads,
                          cfg.attn.head_dim, dtype, ring_window=ring,
                          quant=cfg.kv_quant)
    elif spec.mixer == "mla":
        c = init_mla_cache(cfg.mla, batch, max_len, dtype,
                           quant=cfg.kv_quant)
    elif spec.mixer == "ssm":
        c = init_ssm_cache(cfg.ssm, cfg.d_model, batch, dtype)
    elif spec.mixer == "rec":
        c = init_rglru_cache(cfg.rglru, cfg.d_model, batch, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.cross_attn:
        e = cfg.encoder
        hd = cfg.attn.head_dim
        c = dict(c)
        c["xk"] = jnp.zeros((batch, e.n_frames, cfg.attn.n_kv_heads, hd), dtype)
        c["xv"] = jnp.zeros((batch, e.n_frames, cfg.attn.n_kv_heads, hd), dtype)
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Per-segment stacked cache tree (leading dim = segment repeat)."""
    segs = segment_layers(cfg.block_specs())
    out = []
    for block, rep in segs:
        seg = {}
        for bi, spec in enumerate(block):
            c = _block_cache(cfg, spec, batch, max_len, dtype)
            seg[f"b{bi}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (rep,) + a.shape), c
            )
        out.append(seg)
    return out


# ------------------------------------------------------------- block apply


def _cross_attention(cfg: ModelConfig, p, x, xk, xv):
    """Decoder->encoder cross attention (no mask, no rope)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    out = blockwise_attention(
        q, xk, xv,
        q_positions=jnp.arange(x.shape[1]),
        k_positions=jnp.arange(xk.shape[1]),
        causal=False,
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def _keep_inactive(new, old, active):
    """Recurrent state: slots whose ``active`` is False keep ``old``."""
    if active is None:
        return new
    return jax.tree.map(
        lambda n, o: jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o), new, old)


def _apply_block(cfg: ModelConfig, spec: BlockSpec, p, x, *, positions, mode,
                 cache, prefix_len, enc_out, kernel_impl="xla",
                 continuation=False, active=None):
    """One layer. mode: "train" | "prefill" | "decode".

    Returns (x, cache).  In decode mode the cache returned holds only what
    the step changes: an attention layer's entries for the new token
    (written by :func:`_commit_decode` after the layer loop), an MLA
    layer's latent cache with the token written, a recurrent layer's
    state; slots whose ``active`` is False change nothing.
    """
    h = _apply_norm(cfg, p["ln1"], x)
    if cache is None:
        new_cache = None
    else:
        new_cache = {} if mode == "decode" else dict(cache)
    if spec.mixer in ("attn", "attn_local"):
        local = spec.mixer == "attn_local"
        kv_keys = ("k", "v", "pos") + (
            ("k_s", "v_s") if cache is not None and "k_s" in cache else ())
        if mode == "decode":
            sub = {k: cache[k] for k in kv_keys}
            out, nc = attention_decode(cfg.attn, p["attn"], h, positions, sub,
                                       local=local)
            new_cache.update(nc)
        else:
            sub = ({k: cache[k] for k in kv_keys}
                   if cache is not None else None)
            out, nc = attention_prefill(
                cfg.attn, p["attn"], h, positions, local=local, cache=sub,
                prefix_len=prefix_len, kernel_impl=kernel_impl,
                continuation=continuation)
            if nc is not None:
                new_cache.update(nc)
    elif spec.mixer == "mla":
        mla_keys = ("c_kv", "k_rope") + (
            ("c_s", "r_s") if cache is not None and "c_s" in cache else ())
        sub = ({k: cache[k] for k in mla_keys}
               if cache is not None else None)
        if mode == "decode":
            out, nc = mla_decode(cfg.mla, p["mla"], h, positions, sub,
                                 active=active)
            new_cache.update(nc)
        else:
            out, nc = mla_prefill(cfg.mla, p["mla"], h, positions, cache=sub,
                                  continuation=continuation)
            if nc is not None:
                new_cache.update(nc)
    elif spec.mixer == "ssm":
        sub = ({k: cache[k] for k in ("conv", "ssm")}
               if cache is not None else None)
        if mode == "decode":
            out, nc = ssm_decode(cfg.ssm, p["ssm"], h, sub)
            new_cache.update(_keep_inactive(nc, sub, active))
        else:
            out, nc = ssm_forward(cfg.ssm, p["ssm"], h, cache=sub)
            if nc is not None:
                new_cache.update(nc)
    elif spec.mixer == "rec":
        sub = ({k: cache[k] for k in ("conv", "h")}
               if cache is not None else None)
        if mode == "decode":
            out, nc = rglru_decode(cfg.rglru, p["rec"], h, sub)
            new_cache.update(_keep_inactive(nc, sub, active))
        else:
            out, nc = rglru_forward(cfg.rglru, p["rec"], h, cache=sub)
            if nc is not None:
                new_cache.update(nc)
    else:
        raise ValueError(spec.mixer)
    x = x + out

    if spec.cross_attn:
        hx = _apply_norm(cfg, p["lnx"], x)
        if mode == "decode":
            xk, xv = cache["xk"], cache["xv"]
        else:
            # project encoder output once; persist in the cache if present
            xk = jnp.einsum("bsd,dhk->bshk", enc_out,
                            p["xattn"]["wk"].astype(x.dtype))
            xv = jnp.einsum("bsd,dhk->bshk", enc_out,
                            p["xattn"]["wv"].astype(x.dtype))
            if new_cache is not None:
                new_cache["xk"], new_cache["xv"] = xk, xv
        x = x + _cross_attention(cfg, p["xattn"], hx, xk, xv)

    if spec.channel == "mlp":
        h = _apply_norm(cfg, p["ln2"], x)
        mp = jax.tree.map(lambda a: a.astype(x.dtype), p["mlp"])
        x = x + apply_mlp(mp, h, cfg.mlp_act)
    elif spec.channel == "moe":
        h = _apply_norm(cfg, p["ln2"], x)
        x = x + apply_moe(cfg.moe, p["moe"], h)
    return x, new_cache


# --------------------------------------------------------------- backbone


def _commit_decode(block, seg_c, seg_new, positions, active):
    """A segment's caches after a decode step: each attention layer's
    token entries written into the stacked cache in place, every other
    layer's changed leaves taken as they are."""
    out = {}
    for bi, spec in enumerate(block):
        c, new = seg_c[f"b{bi}"], seg_new[f"b{bi}"]
        if spec.mixer in ("attn", "attn_local"):
            out[f"b{bi}"] = cache_write_decode(c, new, positions, active)
        else:
            out[f"b{bi}"] = dict(c, **new)
    return out


def _run_segments(cfg: ModelConfig, params, x, *, positions, mode, caches,
                  prefix_len, enc_out, unroll, kernel_impl="xla",
                  remat=False, continuation=False, active=None):
    segs = segment_layers(cfg.block_specs())
    new_caches = [] if caches is not None else None
    for si, (block, rep) in enumerate(segs):
        seg_p = params[f"seg{si}"]
        seg_c = caches[si] if caches is not None else None

        def body(x, p_slice, c_slice):
            nc = {} if c_slice is not None else None
            for bi, spec in enumerate(block):
                x, c = _apply_block(
                    cfg, spec, p_slice[f"b{bi}"], x, positions=positions,
                    mode=mode, cache=(c_slice[f"b{bi}"] if c_slice else None),
                    prefix_len=prefix_len, enc_out=enc_out,
                    kernel_impl=kernel_impl, continuation=continuation,
                    active=active)
                if nc is not None:
                    nc[f"b{bi}"] = c
            return x, nc

        if remat:
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )

        nc = None
        if unroll or rep == 1:
            ncs = []
            for r in range(rep):
                p_r = jax.tree.map(lambda a: a[r], seg_p)
                c_r = (jax.tree.map(lambda a: a[r], seg_c)
                       if seg_c is not None else None)
                x, nc_r = body(x, p_r, c_r)
                ncs.append(nc_r)
            if seg_c is not None:
                nc = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)
        elif seg_c is None:
            def step(carry, p_slice):
                y, _ = body(carry, p_slice, None)
                return y, ()
            x, _ = jax.lax.scan(step, x, seg_p)
        else:
            def step(carry, inp):
                p_slice, c_slice = inp
                y, nc = body(carry, p_slice, c_slice)
                return y, nc
            x, nc = jax.lax.scan(step, x, (seg_p, seg_c))
        if seg_c is not None:
            if mode == "decode":
                nc = _commit_decode(block, seg_c, nc, positions, active)
            new_caches.append(nc)
    return x, new_caches


def _logits(cfg: ModelConfig, params, x):
    x = _apply_norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        w = params["embed"].astype(x.dtype).T
    else:
        w = params["unembed"].astype(x.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    if cfg.logit_softcap is not None:
        logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return logits


def _embed(cfg: ModelConfig, params, tokens, positions, prefix_embeds):
    x = params["embed"][tokens]
    if cfg.scale_embed:
        x = x * np.sqrt(cfg.d_model).astype(np.float32)
    if "pos_embed" in params:
        x = x + params["pos_embed"][positions]
    x = x.astype(jnp.bfloat16 if cfg.param_dtype == "bfloat16" else x.dtype)
    prefix_len = None
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        prefix_len = prefix_embeds.shape[1]
    return x, prefix_len


def encoder_forward(cfg: ModelConfig, params, frames, *, unroll=False):
    """Whisper-style encoder over stub frame embeddings (B, n_frames, d)."""
    e = cfg.encoder
    p = params["encoder"]
    x = frames + p["pos"].astype(frames.dtype)[None]
    acfg = cfg.attn.__class__(
        n_heads=e.n_heads, n_kv_heads=e.n_heads,
        head_dim=e.d_model // e.n_heads, rope=False, causal=False)

    def step(x, lp):
        h = _apply_norm(cfg, lp["ln1"], x)
        out, _ = attention_prefill(
            acfg, lp["attn"], h, jnp.arange(e.n_frames)[None], local=False)
        x = x + out
        h = _apply_norm(cfg, lp["ln2"], x)
        mp = jax.tree.map(lambda a: a.astype(x.dtype), lp["mlp"])
        return x + apply_mlp(mp, h, "gelu"), ()

    if unroll:
        for r in range(e.n_layers):
            x, _ = step(x, jax.tree.map(lambda a: a[r], p["layers"]))
    else:
        x, _ = jax.lax.scan(step, x, p["layers"])
    return _apply_norm(cfg, p["final_norm"], x)


# ------------------------------------------------------------ entry points


def forward_train(cfg: ModelConfig, params, tokens, *, prefix_embeds=None,
                  enc_frames=None, unroll=False, remat=False):
    """Teacher-forced logits (B, S[, +prefix], V)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    enc_out = None
    if cfg.encoder is not None:
        enc_out = encoder_forward(cfg, params, enc_frames, unroll=unroll)
    x, prefix_len = _embed(cfg, params, tokens, positions, prefix_embeds)
    if prefix_len:
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1])[None], (B, x.shape[1]))
    x, _ = _run_segments(cfg, params, x, positions=positions, mode="train",
                         caches=None, prefix_len=prefix_len, enc_out=enc_out,
                         unroll=unroll, remat=remat)
    if prefix_len:
        x = x[:, prefix_len:]
    return _logits(cfg, params, x), x


def loss_fn(cfg: ModelConfig, params, tokens, labels, *, prefix_embeds=None,
            enc_frames=None, unroll=False, remat=False):
    """Mean next-token cross entropy; labels < 0 are masked out.

    With ``cfg.mtp`` adds DeepSeek-V3-style multi-token prediction: a second
    head predicts token t+2 from [hidden_t ; embed(label_t)].
    """
    logits, hidden = forward_train(
        cfg, params, tokens, prefix_embeds=prefix_embeds,
        enc_frames=enc_frames, unroll=unroll, remat=remat)
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    lab = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    if cfg.mtp:
        # predict labels shifted one more step (t+2 target from position t)
        emb_next = params["embed"][lab]
        if cfg.scale_embed:
            emb_next = emb_next * np.sqrt(cfg.d_model).astype(np.float32)
        h2 = jnp.concatenate([hidden, emb_next.astype(hidden.dtype)], axis=-1)
        h2 = h2 @ params["mtp"]["proj"].astype(hidden.dtype)
        h2 = _apply_norm(cfg, params["mtp"]["norm"], h2)
        logits2 = _logits(cfg, params, h2).astype(jnp.float32)
        lab2 = jnp.concatenate(
            [lab[:, 1:], jnp.zeros_like(lab[:, :1])], axis=1)
        mask2 = jnp.concatenate(
            [mask[:, 1:], jnp.zeros_like(mask[:, :1])], axis=1)
        logp2 = jax.nn.log_softmax(logits2, axis=-1)
        nll2 = -jnp.take_along_axis(logp2, lab2[..., None], axis=-1)[..., 0]
        loss = loss + 0.3 * (nll2 * mask2).sum() / jnp.maximum(mask2.sum(), 1.)
    return loss


def forward_prefill(cfg: ModelConfig, params, tokens, positions, caches, *,
                    prefix_embeds=None, enc_frames=None, unroll=False,
                    kernel_impl="xla", continuation=False, last_index=None):
    """Prefill a chunk; returns (last-position logits, new caches).

    positions: (B, S) absolute positions of ``tokens`` (supports chunked /
    continued prefill).  ``last_index`` (may be traced) picks the token
    whose logits are returned when the chunk ends in padding.
    """
    enc_out = None
    if cfg.encoder is not None:
        enc_out = encoder_forward(cfg, params, enc_frames, unroll=unroll)
    x, prefix_len = _embed(cfg, params, tokens, positions, prefix_embeds)
    if prefix_len:
        B = tokens.shape[0]
        positions = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(prefix_len)[None], (B, prefix_len)),
             positions + prefix_len], axis=1)
    x, new_caches = _run_segments(
        cfg, params, x, positions=positions, mode="prefill", caches=caches,
        prefix_len=prefix_len, enc_out=enc_out, unroll=unroll,
        kernel_impl=kernel_impl, continuation=continuation)
    if last_index is None:
        x_last = x[:, -1:]
    else:
        x_last = jax.lax.dynamic_slice_in_dim(
            x, (prefix_len or 0) + last_index, 1, axis=1)
    return _logits(cfg, params, x_last), new_caches


def forward_decode(cfg: ModelConfig, params, tokens, positions, caches, *,
                   active=None, unroll=False):
    """One-token decode. tokens (B, 1); positions (B,) current index.

    ``active`` (B,) bool, if given: slots where it is False still compute
    but leave every byte of their caches as it was.
    """
    x = params["embed"][tokens]
    if cfg.scale_embed:
        x = x * np.sqrt(cfg.d_model).astype(np.float32)
    if "pos_embed" in params:
        x = x + params["pos_embed"][positions][:, None]
    x = x.astype(jnp.bfloat16 if cfg.param_dtype == "bfloat16" else x.dtype)
    x, new_caches = _run_segments(
        cfg, params, x, positions=positions, mode="decode", caches=caches,
        prefix_len=None, enc_out=None, unroll=unroll, active=active)
    return _logits(cfg, params, x), new_caches


def greedy_generate(cfg: ModelConfig, params, prompt, n_tokens: int, *,
                    max_len: int) -> list:
    """Plain greedy generation for one request: prefill the whole prompt
    at batch 1, then decode one token at a time.

    The reference the served path is checked against: no slots, chunks,
    masking or KV migration.  Returns the ``n_tokens`` greedy token ids.
    """
    P = len(prompt)
    prefill = jax.jit(lambda p, t, pos, c: forward_prefill(cfg, p, t, pos, c))
    decode = jax.jit(lambda p, t, pos, c: forward_decode(cfg, p, t, pos, c))
    caches = init_cache(cfg, 1, max_len, jnp.dtype(cfg.param_dtype))
    logits, caches = prefill(params, jnp.asarray(prompt, jnp.int32)[None],
                             jnp.arange(P, dtype=jnp.int32)[None], caches)
    out = [int(jnp.argmax(logits[0, -1]))]
    for i in range(n_tokens - 1):
        logits, caches = decode(params, jnp.full((1, 1), out[-1], jnp.int32),
                                jnp.full((1,), P + i, jnp.int32), caches)
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


def init_model(cfg: ModelConfig, key, dtype=jnp.float32):
    return init_params(model_defs(cfg), key, dtype)


def param_count(cfg: ModelConfig) -> int:
    from .params import _walk

    return sum(int(np.prod(d.shape)) for _, d in _walk(model_defs(cfg)))


def active_param_count(cfg: ModelConfig) -> int:
    """Active params per token (MoE: top_k+shared experts only)."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    from .params import _walk

    moe_layer = moe_defs(cfg.moe, cfg.d_model)
    routed = sum(
        int(np.prod(d.shape)) for path, d in _walk(moe_layer)
        if path[0] in ("w_gate", "w_up", "w_down"))
    n_moe_layers = sum(
        1 for s in cfg.block_specs() if s.channel == "moe")
    active_frac = cfg.moe.top_k / cfg.moe.n_experts
    return int(total - n_moe_layers * routed * (1 - active_frac))
