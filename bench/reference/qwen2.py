"""Plain qwen2 forward pass, and the weights the benchmark serves.

The reference follows the published architecture (arXiv:2407.10671; the
Hugging Face ``Qwen2ForCausalLM``): token embedding, then per layer
RMSNorm, grouped-query attention with biases on q, k and v and rotary
positions (``rotate_half`` form, base ``rope_theta``), a residual add,
RMSNorm, a SwiGLU MLP and a residual add; a final RMSNorm and logits
through the tied embedding.  It is written in ``jax.numpy`` in float32 with
every matrix product at ``Precision.HIGHEST``, and imports nothing of the
program.  It runs over a sequence in blocks of rows against a float32 KV
cache, so that an 8192-token sequence fits beside the served weights.

Departure, in naming only: the program keeps each RMSNorm weight as
``1 + scale``; the reference reads the weight that way.

``make_params`` builds the weights from a seed, on the device, in one
jitted call, in the layout and dtype the program serves.  The reference
reads the same arrays; nothing the program computes is passed to it.

``control=True`` computes the same pass with every linear layer's inputs
rounded to float8 (e4m3, one scale per weight tensor and per activation
row): the lower precision that the benchmark's check must reject.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["make_params", "param_specs", "max_logit_gap"]

_HI = jax.lax.Precision.HIGHEST
_BIAS_STD = 0.1
_NORM_STD = 0.1


def _dims(c: dict) -> tuple:
    return (int(c["num_hidden_layers"]), int(c["hidden_size"]),
            int(c["num_attention_heads"]), int(c["num_key_value_heads"]),
            int(c["head_dim"]), int(c["intermediate_size"]),
            int(c["vocab_size"]))


def param_specs(c: dict) -> dict:
    """``path -> (shape, std)`` of every weight, in the program's layout
    (layers stacked on a leading axis under ``seg0/b0``)."""
    L, d, H, KV, hd, F, V = _dims(c)
    b = ("seg0", "b0")
    return {
        ("embed",): ((V, d), 0.02),
        ("final_norm", "scale"): ((d,), _NORM_STD),
        b + ("ln1", "scale"): ((L, d), _NORM_STD),
        b + ("attn", "wq"): ((L, d, H, hd), d ** -0.5),
        b + ("attn", "wk"): ((L, d, KV, hd), d ** -0.5),
        b + ("attn", "wv"): ((L, d, KV, hd), d ** -0.5),
        b + ("attn", "wo"): ((L, H, hd, d), (H * hd) ** -0.5),
        b + ("attn", "bq"): ((L, H, hd), _BIAS_STD),
        b + ("attn", "bk"): ((L, KV, hd), _BIAS_STD),
        b + ("attn", "bv"): ((L, KV, hd), _BIAS_STD),
        b + ("ln2", "scale"): ((L, d), _NORM_STD),
        b + ("mlp", "w_gate"): ((L, d, F), d ** -0.5),
        b + ("mlp", "w_up"): ((L, d, F), d ** -0.5),
        b + ("mlp", "w_down"): ((L, F, d), F ** -0.5),
    }


def key_from_seed(seed: int):
    """A PRNG key from any whole number (the run's seed may exceed 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def make_params(c: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    specs = param_specs(c)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(specs))
        tree: dict = {}
        for (path, (shape, std)), k in zip(specs.items(), keys):
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = (std * jax.random.normal(k, shape, dtype)
                              ).astype(dtype)
        return tree

    return build(key_from_seed(seed))


# ------------------------------------------------------------ the pass
def _fp8(x, axes):
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(eq, x, w, control):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if control:
        x = _fp8(x, axes=tuple(range(1, x.ndim)))       # per row
        w = _fp8(w, axes=tuple(range(w.ndim)))          # per tensor
    return jnp.einsum(eq, x, w, precision=_HI)


def _rms(x, scale, eps):
    w = 1.0 + scale.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _hidden(c, params, kc, vc, toks, pos0, control):
    """Rows ``pos0 .. pos0+T-1`` through every layer; writes the cache."""
    L, d, H, KV, hd, F, V = _dims(c)
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    T, S = toks.shape[0], kc.shape[1]
    pos = pos0 + jnp.arange(T)
    x = params["embed"][toks].astype(jnp.float32)
    visible = jnp.arange(S)[None, :] <= pos[:, None]          # (T, S)
    lp = params["seg0"]["b0"]

    def layer(x, inp):
        p, k_l, v_l = inp
        a = p["attn"]
        h = _rms(x, p["ln1"]["scale"], eps)
        q = _linear("td,dhk->thk", h, a["wq"], control) + a["bq"]
        k = _linear("td,dhk->thk", h, a["wk"], control) + a["bk"]
        v = _linear("td,dhk->thk", h, a["wv"], control) + a["bv"]
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k_l = jax.lax.dynamic_update_slice(k_l, k, (pos0, 0, 0))
        v_l = jax.lax.dynamic_update_slice(v_l, v.astype(jnp.float32),
                                           (pos0, 0, 0))
        qg = q.reshape(T, KV, H // KV, hd)
        s = jnp.einsum("tkgd,skd->kgts", qg, k_l, precision=_HI) / np.sqrt(hd)
        s = jnp.where(visible[None, None], s, -jnp.inf)
        o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v_l,
                       precision=_HI).reshape(T, H, hd)
        x = x + _linear("thk,hkd->td", o, a["wo"], control)
        h = _rms(x, p["ln2"]["scale"], eps)
        m = p["mlp"]
        g = jax.nn.silu(_linear("td,df->tf", h, m["w_gate"], control))
        u = _linear("td,df->tf", h, m["w_up"], control)
        x = x + _linear("tf,fd->td", g * u, m["w_down"], control)
        return x, (k_l, v_l)

    x, (kc, vc) = jax.lax.scan(layer, x, (lp, kc, vc))
    return _rms(x, params["final_norm"]["scale"], eps), kc, vc


def _logits(params, h, control):
    return _linear("td,vd->tv", h, params["embed"], control)


@lru_cache(maxsize=None)
def _block_fn(cfg_items: tuple, control: bool):
    c = dict(cfg_items)

    def run(params, caches, toks, pos0, targets, valid):
        kc, vc = caches[0], caches[1]
        h, kc, vc = _hidden(c, params, kc, vc, toks, pos0, False)
        lg = _logits(params, h, False)
        best = jnp.max(lg, -1)
        served = jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
        gap = jnp.max(jnp.where(valid, best - served, -jnp.inf))
        out = [kc, vc]
        if not control:
            return out, gap, jnp.float32(-jnp.inf)
        h8, kc8, vc8 = _hidden(c, params, caches[2], caches[3], toks, pos0,
                               True)
        first8 = jnp.argmax(_logits(params, h8, True), -1)
        gap8 = best - jnp.take_along_axis(lg, first8[:, None], -1)[:, 0]
        return (out + [kc8, vc8], gap,
                jnp.max(jnp.where(valid, gap8, -jnp.inf)))

    return jax.jit(run)


def max_logit_gap(c: dict, params, seqs, *, max_len: int, block: int = 256,
                  control: bool = False) -> dict:
    """Widest gap of served tokens below the reference's best logit.

    ``seqs`` holds ``(prompt, served)`` pairs.  The served token at each
    position is read against the float32 reference's logits after the
    prompt and the tokens served before it.  With ``control`` the same
    positions are also read for the token that the float8 pass puts
    first (``control_gap``).
    """
    L, d, H, KV, hd, F, V = _dims(c)
    fn = _block_fn(tuple(sorted((k, v) for k, v in c.items()
                                if isinstance(v, (int, float, str)))),
                   bool(control))
    shape = (L, max_len, KV, hd)
    per_seq, per_seq8, n_tok = [], [], 0
    for prompt, served in seqs:
        gaps, gaps8 = [], []
        full = np.concatenate([np.asarray(prompt, np.int64),
                               np.asarray(served, np.int64)])
        P, n = len(prompt), len(full) - 1
        if n > max_len:
            raise ValueError(f"sequence of {n} tokens over max_len {max_len}")
        n_pad = -(-n // block) * block
        toks = np.zeros(n_pad, np.int32)
        toks[:n] = full[:-1]
        tgt = np.zeros(n_pad, np.int32)
        tgt[:n] = full[1:]
        valid = np.zeros(n_pad, bool)
        valid[P - 1:n] = True
        n_tok += int(valid.sum())
        caches = [jnp.zeros(shape, jnp.float32)
                  for _ in range(4 if control else 2)]
        for s in range(0, n_pad, block):
            caches, g, g8 = fn(params, caches, jnp.asarray(toks[s:s + block]),
                               jnp.int32(s), jnp.asarray(tgt[s:s + block]),
                               jnp.asarray(valid[s:s + block]))
            gaps.append(g)
            gaps8.append(g8)
        per_seq.append(jnp.max(jnp.stack(gaps)))
        per_seq8.append(jnp.max(jnp.stack(gaps8)))
    per_seq = [float(g) for g in per_seq]
    out = {"max_logit_gap": max(per_seq), "per_sequence": per_seq,
           "tokens": n_tok}
    if control:
        out["control_gap"] = max(float(g) for g in per_seq8)
    return out
