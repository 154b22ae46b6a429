"""Training substrate tests: optimizer, train step, checkpoint/restart,
gradient compression, data determinism."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.training import (DataConfig, OptConfig, SyntheticLM,
                            init_train_state, make_train_step)
from repro.training.compress import dequantize_int8, quantize_int8
from repro.launch.train import preset_100m, run_training


def test_loss_decreases_small_model(tmp_path):
    cfg = get_config("qwen2-0.5b", reduced=True)
    opt = OptConfig(lr=2e-3, warmup_steps=5, total_steps=60)
    state = init_train_state(cfg, jax.random.PRNGKey(0), opt)
    step = jax.jit(make_train_step(cfg, opt))
    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, batch=8,
                                seq_len=64))
    losses = []
    for i in range(30):
        b = {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05


def test_microbatch_equals_full_batch_grads():
    """Grad accumulation over microbatches == single big batch (linearity)."""
    cfg = get_config("qwen2-0.5b", reduced=True)
    opt = OptConfig()
    state = init_train_state(cfg, jax.random.PRNGKey(0), opt)
    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, batch=4,
                                seq_len=32))
    b = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
    s1, m1 = jax.jit(make_train_step(cfg, opt, microbatches=1))(state, b)
    s2, m2 = jax.jit(make_train_step(cfg, opt, microbatches=2))(state, b)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    l1 = jax.tree.leaves(s1["params"])
    l2 = jax.tree.leaves(s2["params"])
    for a, b_ in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-5)


def test_checkpoint_restart_resumes_exactly(tmp_path):
    """Kill/restart: resumed run produces the same final loss."""
    cfg = preset_100m().replace(n_layers=2, d_model=64, d_ff=128,
                                vocab_size=512)
    kw = dict(steps=8, batch=2, seq_len=32, ckpt_every=4, log_every=100)
    full = run_training(cfg, ckpt_dir=None, **kw)
    # run 8 steps with a checkpoint at 4, then "crash" and resume
    d = str(tmp_path / "ck")
    run_training(cfg, ckpt_dir=d, **dict(kw, steps=4))
    resumed = run_training(cfg, ckpt_dir=d, **kw)
    np.testing.assert_allclose(resumed["final_loss"], full["final_loss"],
                               rtol=1e-4)


def test_int8_error_feedback_roundtrip():
    x = np.random.default_rng(0).normal(size=(64, 64)).astype(np.float32)
    q, s = quantize_int8(jnp.asarray(x))
    back = dequantize_int8(q, s)
    # quantisation error bounded by scale/2 per element
    assert float(jnp.max(jnp.abs(back - x))) <= float(s) * 0.5 + 1e-6


def test_compressed_psum_preserves_mean_with_feedback():
    """Over repeated steps, error feedback keeps the compressed mean
    unbiased: accumulated residuals stay bounded."""
    import os
    from repro.training.compress import make_compressed_psum
    # single-device shard_map over a size-1 axis still exercises the path
    mesh = jax.make_mesh((1,), ("data",))
    f = make_compressed_psum(mesh, "data")
    from jax.sharding import PartitionSpec as P
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(32,))
                          .astype(np.float32))}
    r = {"w": jnp.zeros((32,), jnp.float32)}
    fn = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    total = jnp.zeros((32,))
    for _ in range(50):
        mean, r = fn(g, r)
        total = total + mean["w"]
    # with error feedback, sum of outputs ~ 50 * g (residual bounded)
    np.testing.assert_allclose(np.asarray(total / 50), np.asarray(g["w"]),
                               atol=2e-3)


def test_data_pipeline_deterministic_resume():
    cfg = DataConfig(vocab_size=1000, batch=2, seq_len=64, seed=3)
    a = SyntheticLM(cfg).batch_at(17)
    b = SyntheticLM(cfg).batch_at(17)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["labels"], b["labels"])
    # next-token alignment
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
