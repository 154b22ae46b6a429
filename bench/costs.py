"""Operations and HBM bytes of the served programs, from configuration shapes.

The least work a step needs: every weight read once, every live key and
value read once and each new one written once, and the multiply-adds of
the tokens the step processes (two FLOPs each).  Attention is counted at
the live lengths, not at the padded cache; logits are counted where the
step returns them (one row per decoding slot, one per prefill chunk).
These are the numerators of ``decode_roofline`` and ``served_mfu``.
"""

from __future__ import annotations

__all__ = ["Shapes", "decode_step", "mixed_step", "least_seconds"]

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


class Shapes:
    """The sizes of a qwen2-style configuration file."""

    def __init__(self, c: dict):
        self.L = int(c["num_hidden_layers"])
        self.d = int(c["hidden_size"])
        self.H = int(c["num_attention_heads"])
        self.KV = int(c["num_key_value_heads"])
        self.hd = int(c["head_dim"])
        self.F = int(c["intermediate_size"])
        self.V = int(c["vocab_size"])
        self.bpe = _DTYPE_BYTES[c["torch_dtype"]]
        d, H, KV, hd, F = self.d, self.H, self.KV, self.hd, self.F
        # multiply-add weights of one layer: q, k, v, o and the SwiGLU MLP
        self.layer_matmul = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
        # every weight of one layer: the above, q/k/v biases, two norms
        self.layer_params = self.layer_matmul + (H + 2 * KV) * hd + 2 * d
        # embedding (tied: also the output head) and the final norm
        self.weight_bytes = self.bpe * (self.L * self.layer_params
                                        + self.V * d + d)
        self.kv_bytes_per_token = self.bpe * self.L * 2 * KV * hd

    def token_flops(self) -> float:
        """Linear layers of one token through every layer (no logits)."""
        return 2.0 * self.L * self.layer_matmul

    def logits_flops(self) -> float:
        return 2.0 * self.d * self.V

    def attention_flops(self, keys: float) -> float:
        """Scores and weighted values of one query row over ``keys`` keys,
        summed over rows: pass the total number of (row, key) pairs."""
        return 4.0 * self.L * self.H * self.hd * keys


def decode_step(s: Shapes, n_live: int, live_len_sum: int) -> tuple:
    """``(flops, bytes)`` of one token for each of ``n_live`` slots whose
    lengths, the new token included, sum to ``live_len_sum``."""
    flops = (n_live * (s.token_flops() + s.logits_flops())
             + s.attention_flops(live_len_sum))
    bytes_ = s.weight_bytes + s.kv_bytes_per_token * live_len_sum
    return flops, float(bytes_)


def mixed_step(s: Shapes, done: int, n: int, n_live: int,
               live_len_sum: int) -> tuple:
    """``(flops, bytes)`` of a prefill chunk of ``n`` tokens after ``done``
    cached ones, fused with one decode token for ``n_live`` slots."""
    pairs = n * done + n * (n + 1) // 2
    flops = (n * s.token_flops() + s.logits_flops() + s.attention_flops(pairs)
             + n_live * (s.token_flops() + s.logits_flops())
             + s.attention_flops(live_len_sum))
    bytes_ = (s.weight_bytes
              + s.kv_bytes_per_token * (done + n + live_len_sum))
    return flops, float(bytes_)


def least_seconds(flops: float, bytes_: float, peaks: dict) -> tuple:
    """``(seconds, bound)``: the larger of the compute and memory times."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
