"""Mamba-2 SSD chunk-scan kernel for TPU.

State-space duality re-tiled for the MXU: the sequence is cut into chunks
of Q tokens; within a chunk the output is an attention-like (Q,Q) masked
matmul (dual form, MXU-friendly); across chunks a (P,N) state per head is
carried in VMEM scratch along the innermost-sequential grid axis -- the
recurrent part touches VMEM only, which is the TPU translation of Mamba's
SRAM-resident scan.

Grid: (batch, heads, n_chunks).  Blocks: x (Q,P), B/C (Q,N), log_a (Q,1)
-- a column, so the block meets the TPU (8, 128) tiling rule.  Mosaic
has no cumsum, so the in-chunk prefix sums are a lower-triangular
matmul on the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_pallas"]


def _kernel(x_ref, b_ref, c_ref, la_ref, y_ref, hlast_ref, state_ref, *,
            chunk, n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)      # (Q, P)
    Bm = b_ref[0].astype(jnp.float32)        # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)        # (Q, N)
    la = la_ref[0, 0].astype(jnp.float32)    # (Q, 1)

    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    trif = tri.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    # inclusive cumsum as a column (Q,1) and as a row (1,Q)
    cum = jax.lax.dot_general(trif, la, (((1,), (0,)), ((), ())),
                              precision=hi,
                              preferred_element_type=jnp.float32)
    cum_row = jax.lax.dot_general(la, trif, (((0,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)
    # intra-chunk dual form: L[t,s] = exp(cum_t - cum_s) for s <= t
    Lm = jnp.where(tri, jnp.exp(cum - cum_row), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    w = cb * Lm
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,P)
    # inter-chunk: y += diag(exp(cum)) C h_prev
    h = state_ref[...]                       # (P, N)
    ch = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,P)
    y = y + ch * jnp.exp(cum)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h <- exp(cum_Q) h + sum_s exp(cum_Q - cum_s) x_s B_s^T
    last = jnp.sum(la)                       # cum_Q, as a scalar
    xw = x * jnp.exp(last - cum)             # (Q, P)
    hupd = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (P,N)
    state_ref[...] = h * jnp.exp(last) + hupd

    @pl.when(ic == n_chunks - 1)
    def _fin():
        hlast_ref[0, 0] = state_ref[...]


def ssd_scan_pallas(x_dt, Bm, Cm, log_a, *, chunk=256, interpret=False):
    """x_dt (B,S,H,P); Bm/Cm (B,S,N); log_a (B,S,H) ->
    (y (B,S,H,P), final_state (B,H,P,N))."""
    Bsz, S, H, P = x_dt.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    xt = x_dt.transpose(0, 2, 1, 3)   # (B,H,S,P)
    lat = log_a.transpose(0, 2, 1)[..., None]  # (B,H,S,1)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    y, hlast = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, S, P), x_dt.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, Bm, Cm, lat)
    return y.transpose(0, 2, 1, 3), hlast
