"""Timing backends for the calibration grid.

Two backends produce ``tau`` (seconds per engine iteration) for a grid
cell:

* ``"kernels"`` -- times the repo's own Pallas kernels through their
  public :mod:`repro.kernels` ``ops`` wrappers on the attached
  accelerator (warmup + median-of-k ``time.perf_counter``), and adds the
  analytic weight-streaming and launch-overhead terms the attention
  kernels alone cannot see, at the attached chip's peak rates
  (:data:`repro.launch.mesh.DEVICE_PEAKS`; an unknown ``device_kind`` is
  an error).  The kernels run compiled, so this backend needs a TPU.
* ``"roofline"`` -- fully deterministic closed-form fallback: per-
  iteration FLOPs and HBM bytes from the :class:`ModelConfig` shape math
  (the same physics as ``launch/roofline.py``) against
  ``mesh.v5e_constants``.  The *additive* roofline sum (compute + memory
  + overhead, not the max) keeps the surface exactly affine in ``C`` and
  ``K``, so the fitter's R^2 diagnostic is meaningful and the
  no-accelerator path is reproducible bit-for-bit.

``backend="auto"`` picks ``"kernels"`` on TPU and ``"roofline"``
elsewhere.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional

from repro.launch.mesh import device_peaks, v5e_constants
from repro.telemetry.timing import timeit_median

from .grid import CalibrationGrid, GridCell

__all__ = [
    "DEFAULT_OVERHEAD_S",
    "Sample",
    "backend_peaks",
    "collect_samples",
    "iteration_costs",
    "roofline_tau",
    "timeit_median",
]

# Fixed per-iteration launch/dispatch overhead for the analytic backend.
# Chosen at the scale of the paper's measured A100 intercepts (Sec. 6.2:
# alpha = 17.4 ms includes scheduler + launch cost the roofline terms
# cannot see); the exact value only shifts the fitted intercepts, never
# the slopes or the fit quality.
DEFAULT_OVERHEAD_S = 2e-3


@dataclass(frozen=True)
class Sample:
    """One timed grid cell."""

    mode: str  # "mixed" | "solo"
    batch: int
    chunk: int  # prefill chunk C (0 for solo)
    kv: int  # aggregate resident KV tokens K
    tau: float  # seconds per iteration
    backend: str  # "kernels" | "roofline"

    def to_dict(self) -> dict:
        return {"mode": self.mode, "batch": self.batch, "chunk": self.chunk,
                "kv": self.kv, "tau": self.tau, "backend": self.backend}

    @classmethod
    def from_dict(cls, d: dict) -> "Sample":
        return cls(mode=str(d["mode"]), batch=int(d["batch"]),
                   chunk=int(d["chunk"]), kv=int(d["kv"]),
                   tau=float(d["tau"]), backend=str(d["backend"]))


# timeit_median moved to repro.telemetry.timing (one canonical timing
# helper for calibration + every benchmark); re-exported here unchanged.


# --------------------------------------------------------------- analytic
def _dtype_bytes(cfg) -> int:
    return 2 if cfg.param_dtype == "bfloat16" else 4


def _attn_layer_stats(cfg) -> Dict[str, float]:
    """Per-attention-layer width and KV-cache bytes per resident token."""
    n_attn = d_attn = kv_bytes = 0
    specs = cfg.block_specs()
    el = 1 if cfg.kv_quant else _dtype_bytes(cfg)
    for s in specs:
        if s.mixer in ("attn", "attn_local"):
            n_attn += 1
            d_attn = cfg.attn.n_heads * cfg.attn.head_dim
            kv_bytes = 2 * cfg.attn.n_kv_heads * cfg.attn.head_dim * el
            if cfg.kv_quant:  # per-(token, kv-head) fp32 scales, K and V
                kv_bytes += 2 * cfg.attn.n_kv_heads * 4
        elif s.mixer == "mla":
            n_attn += 1
            d_attn = cfg.mla.n_heads * (cfg.mla.qk_nope_dim
                                        + cfg.mla.qk_rope_dim)
            kv_bytes = (cfg.mla.kv_lora_rank
                        + cfg.mla.qk_rope_dim) * _dtype_bytes(cfg)
        # ssm / rec layers carry O(1) state: no per-token KV growth
    return {"n_attn": n_attn, "d_attn": d_attn, "kv_bytes": kv_bytes}


def iteration_costs(cfg, *, tokens: int, kv_tokens: int) -> Dict[str, float]:
    """Closed-form FLOPs and HBM bytes for one engine iteration.

    ``tokens`` = tokens computed this iteration (prefill chunk + one per
    decode stream); ``kv_tokens`` = aggregate resident KV tokens across
    the batch.  Both terms are *linear* in their argument by
    construction, which is exactly the paper's affine-surface claim.
    """
    from repro.models.model import active_param_count

    n_active = active_param_count(cfg)
    st = _attn_layer_stats(cfg)
    flops = 2.0 * n_active * tokens + 4.0 * st["d_attn"] * st["n_attn"] * kv_tokens
    bytes_ = (float(n_active) * _dtype_bytes(cfg)
              + float(st["kv_bytes"]) * kv_tokens)
    return {"flops": flops, "bytes": bytes_}


def roofline_tau(cfg, *, tokens: int, kv_tokens: int,
                 hw: Optional[dict] = None,
                 overhead_s: float = DEFAULT_OVERHEAD_S) -> float:
    """Deterministic analytic iteration time (additive roofline sum)."""
    hw = hw or v5e_constants()
    c = iteration_costs(cfg, tokens=tokens, kv_tokens=kv_tokens)
    return (overhead_s + c["flops"] / hw["peak_flops_bf16"]
            + c["bytes"] / hw["hbm_bw"])


def _cell_tokens(cell: GridCell) -> int:
    # mixed iteration computes the prefill chunk plus one token per
    # decode stream; a solo iteration computes one token per stream.
    return cell.chunk + cell.batch if cell.mode == "mixed" else cell.batch


# ---------------------------------------------------------------- kernels
def _time_kernels_cell(cfg, cell: GridCell, *, reps: int, hw: dict) -> float:
    """Accelerator path: Pallas attention kernels + analytic rest.

    The attention ops see the cell's exact (C, K) shapes; the dense
    weight-stream and launch-overhead terms (shape-independent of C and
    K at fixed batch) come from the same closed form as the roofline
    backend, so both backends fit commensurable surfaces.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.prefill_attention.ops import prefill_attention

    if cfg.attn is None:
        raise ValueError(
            f"kernel backend needs an attention config (model "
            f"{cfg.name!r} has none); use backend='roofline'")
    H, KV, D = cfg.attn.n_heads, cfg.attn.n_kv_heads, cfg.attn.head_dim
    B = cell.batch
    key = jax.random.PRNGKey(0)
    dt = jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32

    # per-stream cache length covering the aggregate K
    S = max(1, math.ceil(cell.kv / B))
    qd = jax.random.normal(key, (B, 1, H, D), dt)
    kc = jax.random.normal(key, (B, S, KV, D), dt)
    vc = jax.random.normal(key, (B, S, KV, D), dt)
    kv_len = jnp.full((B,), S, jnp.int32)

    def run_decode():
        decode_attention(qd, kc, vc, kv_len).block_until_ready()

    tau = timeit_median(run_decode, reps=reps)

    if cell.mode == "mixed" and cell.chunk > 0:
        qp = jax.random.normal(key, (1, cell.chunk, H, D), dt)
        kp = jax.random.normal(key, (1, cell.chunk, KV, D), dt)

        def run_prefill():
            prefill_attention(qp, kp, kp).block_until_ready()

        tau += timeit_median(run_prefill, reps=reps)

    # analytic weight-stream + launch terms (attention already measured)
    from repro.models.model import active_param_count
    n_active = active_param_count(cfg)
    tau += (DEFAULT_OVERHEAD_S
            + 2.0 * n_active * _cell_tokens(cell) / hw["peak_flops_bf16"]
            + float(n_active) * _dtype_bytes(cfg) / hw["hbm_bw"])
    return tau


def _resolve_backend(backend: str) -> str:
    if backend != "auto":
        return backend
    import jax
    return "kernels" if jax.default_backend() == "tpu" else "roofline"


def backend_peaks(backend: str) -> dict:
    """Peak rates a backend's analytic terms are charged against: the
    roofline model's stated v5e, or the attached chip's table entry."""
    if backend == "roofline":
        return v5e_constants()
    import jax
    return device_peaks(jax.devices()[0].device_kind)


def collect_samples(grid: CalibrationGrid, cfg, *, backend: str = "auto",
                    reps: int = 5) -> List[Sample]:
    """Time every grid cell; returns one :class:`Sample` per cell."""
    backend = _resolve_backend(backend)
    if backend not in ("kernels", "roofline"):
        raise ValueError(f"unknown backend {backend!r}")
    hw = backend_peaks(backend)
    out: List[Sample] = []
    for cell in grid.cells():
        if backend == "kernels":
            tau = _time_kernels_cell(cfg, cell, reps=reps, hw=hw)
        else:
            tau = roofline_tau(cfg, tokens=_cell_tokens(cell),
                               kv_tokens=cell.kv)
        out.append(Sample(mode=cell.mode, batch=cell.batch, chunk=cell.chunk,
                          kv=cell.kv, tau=tau, backend=backend))
    return out
