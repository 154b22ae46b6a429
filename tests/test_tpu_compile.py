"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology it is
only told about, so these tests catch what interpret mode cannot: block
shapes off the (8, 128) tiling, primitives Mosaic has no lowering for,
dtypes the TPU compiler does not implement.  Nothing runs; a pass here is
not a chip run.  The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.prefill_attention.ops import prefill_attention
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.models import model as M
from repro.serving.steps import (init_server_state, make_decode_step,
                                 make_mixed_step)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


QWEN = get_config("qwen2-0.5b")


@pytest.mark.parametrize("S", [2048, 300, 100])
def test_decode_kernel_compiles_at_qwen_widths(one_chip, S):
    """S=2048 is a served slot; 300 and 100 are ragged calibration
    lengths (padded to the block, or one block of the whole cache)."""
    H, KV, D = QWEN.attn.n_heads, QWEN.attn.n_kv_heads, QWEN.attn.head_dim
    B, bf = 16, jnp.bfloat16
    compiled = decode_attention.lower(
        _spec((B, 1, H, D), bf, one_chip), _spec((B, S, KV, D), bf, one_chip),
        _spec((B, S, KV, D), bf, one_chip), _spec((B,), jnp.int32, one_chip),
    ).compile()
    assert _has_kernel(compiled)


def test_decode_kernel_compiles_with_ring_window(one_chip):
    H, KV, D = QWEN.attn.n_heads, QWEN.attn.n_kv_heads, QWEN.attn.head_dim
    B, S, bf = 4, 512, jnp.bfloat16
    compiled = jax.jit(
        lambda q, k, v, n, kp, qp: decode_attention(
            q, k, v, n, window=128, k_positions=kp, q_positions=qp)
    ).lower(
        _spec((B, 1, H, D), bf, one_chip), _spec((B, S, KV, D), bf, one_chip),
        _spec((B, S, KV, D), bf, one_chip), _spec((B,), jnp.int32, one_chip),
        _spec((B, S), jnp.int32, one_chip), _spec((B,), jnp.int32, one_chip),
    ).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("S", [256, 100])
def test_prefill_kernel_compiles_at_qwen_widths(one_chip, S):
    H, KV, D = QWEN.attn.n_heads, QWEN.attn.n_kv_heads, QWEN.attn.head_dim
    bf = jnp.bfloat16
    compiled = prefill_attention.lower(
        _spec((1, S, H, D), bf, one_chip), _spec((1, S, KV, D), bf, one_chip),
        _spec((1, S, KV, D), bf, one_chip)).compile()
    assert _has_kernel(compiled)


def test_ssd_scan_kernel_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-130m")
    s = cfg.ssm
    H = s.expand * cfg.d_model // s.head_dim
    B, S, bf = 1, 2048, jnp.bfloat16
    compiled = ssd_scan.lower(
        _spec((B, S, H, s.head_dim), bf, one_chip),
        _spec((B, S, s.d_state), bf, one_chip),
        _spec((B, S, s.d_state), bf, one_chip),
        _spec((B, S, H), jnp.float32, one_chip), chunk=s.chunk).compile()
    assert _has_kernel(compiled)


def _lp_args(sharding):
    S, n, m_ub, m_eq = 8, 6, 5, 2
    f64 = jnp.float64
    return (_spec((S, n), f64, sharding), _spec((S, m_ub, n), f64, sharding),
            _spec((S, m_ub), f64, sharding), _spec((S, m_eq, n), f64, sharding),
            _spec((S, m_eq), f64, sharding))


def test_lp_jax_float64_solve_compiles_where_it_is_placed(one_chip):
    """The float64 interior point runs on the host CPU device, because
    the TPU compiler has no float64 LU decomposition."""
    from repro.core.lp_jax import DEFAULT_ITERS, _ipm_batch, solve_device

    assert solve_device().platform == "cpu"
    with jax.enable_x64(True):
        cpu = SingleDeviceSharding(solve_device())
        _ipm_batch.lower(*_lp_args(cpu), 1e-9, DEFAULT_ITERS).compile()
        with pytest.raises(Exception, match="LuDecomposition"):
            _ipm_batch.lower(*_lp_args(one_chip), 1e-9,
                             DEFAULT_ITERS).compile()


def _served_shapes(cfg, B, max_len, sharding):
    dt = jnp.dtype(cfg.param_dtype)
    params = jax.eval_shape(
        lambda: M.init_model(cfg, jax.random.PRNGKey(0), dt))
    state = jax.eval_shape(lambda: init_server_state(cfg, B, max_len, dt))
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _spec(a.shape, a.dtype, sharding), t)
    return place(params), place(state)


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_served_steps_compile_at_published_width(one_chip, step):
    """qwen2-0.5b's served programs in bf16 at published width, 16 slots
    of 2048 tokens; the layers are scanned, so two of them stand for 24."""
    cfg = QWEN.replace(n_layers=2)
    B, max_len, chunk = 16, 2048, 256
    params, state = _served_shapes(cfg, B, max_len, one_chip)
    i32 = jnp.int32
    if step == "decode":
        lowered = jax.jit(make_decode_step(cfg)).lower(params, state)
    else:
        lowered = jax.jit(make_mixed_step(cfg, chunk)).lower(
            params, state, _spec((), i32, one_chip),
            _spec((chunk,), i32, one_chip), _spec((1, 1), i32, one_chip),
            _spec((), i32, one_chip))
    mem = lowered.compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 1024**3, used
    assert np.isfinite(used)
