"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology it is
only told about, so these tests catch what interpret mode cannot: block
shapes off the (8, 128) tiling, primitives Mosaic has no lowering for,
dtypes the TPU compiler does not implement.  Nothing runs; a pass here is
not a chip run.  The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library.
"""

import os
import re

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
from repro.serving.engine import server_programs
from repro.serving.steps import init_server_state


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


_SERVED: dict = {}


def _served_program(step, sharding):
    """qwen2-0.5b's served program as ``server_programs`` builds it, in
    bf16 at published width, 16 slots of 2048 tokens; the layers are
    scanned, so two of them stand for 24.  Returns (compiled, state
    shapes); compiled once per step."""
    if step not in _SERVED:
        cfg = QWEN.replace(n_layers=2)
        B, max_len, chunk = 16, 2048, 256
        params, state = _served_shapes(cfg, B, max_len, sharding)
        decode, mixed = server_programs(cfg, chunk)
        i32 = jnp.int32
        if step == "decode":
            lowered = decode.lower(params, state)
        else:
            lowered = mixed.lower(
                params, state, _spec((), i32, sharding),
                _spec((chunk,), i32, sharding), _spec((1, 1), i32, sharding),
                _spec((), i32, sharding))
        _SERVED[step] = (lowered.compile(), state)
    return _SERVED[step]


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_served_steps_compile_at_published_width(one_chip, step):
    compiled, _ = _served_program(step, one_chip)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 1024**3, used
    assert np.isfinite(used)


#: ops that may produce an array of a whole K or V leaf's shape: in-place
#: writes into the donated cache, the fusions around them, and plumbing
_IN_PLACE_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
                 "fusion", "scatter", "dynamic-update-slice"}


def _ops_of_shape(hlo_text, dims):
    """(opcode, name) of every instruction, fused ones included, whose
    result has these dimensions."""
    want = ",".join(map(str, dims))
    found = []
    for m in re.finditer(r"(%\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                         hlo_text):
        if m.group(2) == want:
            found.append((m.group(3), m.group(1)))
    return found


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_served_steps_update_the_cache_in_place(one_chip, step):
    """The donated state's KV cache is the program's output buffer, and no
    op selects, copies or builds afresh an array of a whole stacked K or
    V leaf: each step writes its new tokens into the cache where it is."""
    compiled, state = _served_program(step, one_chip)
    mem = compiled.memory_analysis()
    leaves = jax.tree_util.tree_leaves_with_path(state["caches"])
    cache_bytes = sum(a.size * a.dtype.itemsize for _, a in leaves)
    assert mem.alias_size_in_bytes >= cache_bytes, (
        mem.alias_size_in_bytes, cache_bytes)
    kv = [a for path, a in leaves if path[-1].key in ("k", "v")]
    assert kv
    text = compiled.as_text()
    for a in kv:
        bad = [op for op in _ops_of_shape(text, a.shape)
               if op[0] not in _IN_PLACE_OPS]
        assert not bad, (a.shape, bad)
    if step == "decode":
        # the mixed step's chunk activations are larger by nature
        layer_k = kv[0].size // kv[0].shape[0] * kv[0].dtype.itemsize
        assert mem.temp_size_in_bytes < layer_k, (
            mem.temp_size_in_bytes, layer_k)
