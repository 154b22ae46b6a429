"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret mode executes the kernel body in Python on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.prefill_attention.kernel import prefill_attention_pallas
from repro.kernels.prefill_attention.ref import prefill_attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_scan_ref


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 128, 4, 4, 32),    # MHA
    (2, 256, 8, 2, 64),    # GQA
    (1, 512, 4, 1, 64),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kw", [
    dict(causal=True),
    dict(causal=True, window=96),
    dict(causal=True, attn_softcap=50.0),
    dict(causal=True, prefix_len=64),
    dict(causal=False),
])
def test_prefill_attention_sweep(B, S, H, KV, D, dtype, kw):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    out = prefill_attention_pallas(q, k, v, block_q=64, block_k=64,
                                   interpret=True, **kw)
    ref = prefill_attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,KV,D", [
    (2, 256, 4, 4, 32),
    (3, 512, 8, 2, 64),
    (1, 1024, 4, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, S, H, KV, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    kv_len = jnp.array([S // 3 + 1, S, max(1, S // 7)][:B], jnp.int32)
    out = decode_attention_pallas(q, k, v, kv_len, block_s=128,
                                  interpret=True)
    ref = decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_decode_attention_ring_window():
    B, S, H, KV, D = 2, 256, 4, 2, 32
    W = 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    kv_len = jnp.array([200, 256], jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    qpos = kv_len - 1
    out = decode_attention_pallas(q, k, v, kv_len, window=W, k_positions=kpos,
                                  q_positions=qpos, block_s=64,
                                  interpret=True)
    ref = decode_attention_ref(q, k, v, kv_len, window=W, k_positions=kpos,
                               q_positions=qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5,
                               rtol=3e-5)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 128, 2, 16, 16, 32),
    (2, 256, 3, 16, 32, 64),
    (1, 512, 4, 32, 64, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    Bm = (jax.random.normal(ks[1], (B, S, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[2], (B, S, N)) * 0.5).astype(dtype)
    log_a = -jnp.abs(jax.random.normal(ks[3], (B, S, H))) * 0.1
    y, h = ssd_scan_pallas(x, Bm, Cm, log_a, chunk=chunk, interpret=True)
    yr, hr = ssd_scan_ref(x, Bm, Cm, log_a)
    tol = dict(atol=2e-1, rtol=2e-1) if dtype == jnp.bfloat16 else \
        dict(atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-2,
                               rtol=1e-2)


# --------------------------------------------------------------------------
# Calibration-grid parity: the calibration subsystem times the kernels
# through their public ops wrappers at (C, K) shapes the grid produces --
# including odd / non-multiple-of-block edges that exercise the wrappers'
# padding and block-halving logic.  These sweeps guarantee calibration
# never times a kernel whose numerics are unverified at that shape.

# odd prefill chunks C (prime / non-multiple-of-block) + one block edge
CALIB_CHUNKS = (17, 48, 100, 128)
# per-stream cache lengths ceil(K / B) from odd aggregate-KV grid points
CALIB_KV_LENS = (33, 108, 300)


@pytest.mark.parametrize("C", CALIB_CHUNKS)
def test_prefill_ops_parity_at_calibration_chunks(C):
    from repro.kernels.prefill_attention.ops import prefill_attention

    H, KV, D = 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(ks[0], (1, C, H, D))
    k = jax.random.normal(ks[1], (1, C, KV, D))
    v = jax.random.normal(ks[2], (1, C, KV, D))
    out = prefill_attention(q, k, v, interpret=True)
    ref = prefill_attention_ref(q, k, v)
    assert out.shape == ref.shape == (1, C, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("S", CALIB_KV_LENS)
def test_decode_ops_parity_at_calibration_kv(S):
    from repro.kernels.decode_attention.ops import decode_attention

    B, H, KV, D = 4, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    # ragged fills: full cache plus partial residency per stream
    kv_len = jnp.array([S, max(1, S - 1), max(1, S // 2), max(1, S // 3)],
                       jnp.int32)
    out = decode_attention(q, k, v, kv_len, interpret=True)
    ref = decode_attention_ref(q, k, v, kv_len)
    assert out.shape == ref.shape == (B, 1, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("S", (48, 100))
def test_ssd_ops_parity_at_calibration_chunks(S):
    from repro.kernels.ssd_scan.ops import ssd_scan

    B, H, P, N = 1, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    x = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[2], (B, S, N)) * 0.5
    log_a = -jnp.abs(jax.random.normal(ks[3], (B, S, H))) * 0.1
    y, h = ssd_scan(x, Bm, Cm, log_a, interpret=True)
    yr, hr = ssd_scan_ref(x, Bm, Cm, log_a)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=1e-2, rtol=1e-2)


def test_model_attention_pallas_path_matches_xla(monkeypatch):
    """attention_prefill(kernel_impl='pallas') == xla path."""
    import functools

    from repro.kernels.prefill_attention import ops as pf_ops
    from repro.models.attention import attention_prefill, attn_defs
    from repro.models.config import AttentionConfig
    from repro.models.params import init_params

    # the model calls the kernel compiled; on this CPU run it interprets
    monkeypatch.setattr(pf_ops, "prefill_attention", functools.partial(
        pf_ops.prefill_attention, interpret=True))

    cfg = AttentionConfig(n_heads=4, n_kv_heads=2, head_dim=32)
    p = init_params(attn_defs(cfg, 64), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    out_x, _ = attention_prefill(cfg, p, x, pos, local=False)
    out_p, _ = attention_prefill(cfg, p, x, pos, local=False,
                                 kernel_impl="pallas")
    np.testing.assert_allclose(np.asarray(out_x), np.asarray(out_p),
                               atol=2e-4, rtol=2e-4)
