"""Integration tests for the real-compute serving path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.planning import solve_bundled_lp
from repro.core.types import Pricing, ServicePrimitives, WorkloadClass
from repro.models import model as M
from repro.serving.cluster import RealCluster
from repro.serving.engine import ServerEngine, SlotRequest, server_programs
from repro.serving.steps import (init_server_state, make_decode_step,
                                 make_mixed_step, make_prefill_step)


def _mk(arch="qwen2-0.5b"):
    cfg = get_config(arch, reduced=True)
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_mixed_step_prefill_isolation():
    """A mixed iteration must not corrupt co-resident decode slots."""
    cfg, params = _mk()
    B, max_len, C = 4, 128, 16
    mixed = jax.jit(make_mixed_step(cfg, C))
    dec = jax.jit(make_decode_step(cfg))

    # two engines with the same two active decode slots; one also prefills
    def setup():
        st = init_server_state(cfg, B, max_len, jnp.float32)
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, 8), 2,
                                  cfg.vocab_size)
        pos = jnp.broadcast_to(jnp.arange(8)[None], (B, 8))
        pf = make_prefill_step(cfg)
        caches, nxt = pf(params, st["caches"], toks, pos)
        st = dict(st, caches=caches,
                  length=jnp.full((B,), 8, jnp.int32),
                  last_token=nxt,
                  active=jnp.array([True, True, False, False]))
        return st

    s_solo = dec(params, setup())[0]
    chunk = jax.random.randint(jax.random.PRNGKey(2), (C,), 2,
                               cfg.vocab_size)
    s_mixed, dec_tokens, _ = mixed(params, setup(), 3, chunk,
                                   jnp.zeros((1, 1), jnp.int32))
    # decode slots 0 and 1 advanced identically in both modes
    np.testing.assert_array_equal(np.asarray(s_solo["last_token"][:2]),
                                  np.asarray(s_mixed["last_token"][:2]))
    np.testing.assert_array_equal(np.asarray(s_solo["length"][:2]),
                                  np.asarray(s_mixed["length"][:2]))


@pytest.mark.parametrize("arch,kv_quant", [
    ("qwen2-0.5b", False), ("qwen2-0.5b", True),
    ("gemma2-2b", False),          # local layers' ring caches
    ("recurrentgemma-2b", False),  # RG-LRU state beside local attention
    ("mamba2-130m", False),        # SSM state
    ("deepseek-v3-671b", False),   # MLA latent cache
])
def test_decode_leaves_inactive_slot_bytes_unchanged(arch, kv_quant):
    """Under the donated ``server_programs`` pair, a decode step leaves
    every byte of an inactive slot's cache (K, V, their scales and
    ``pos``, or its recurrent state) as it was, and writes the active
    slot's."""
    cfg, params = _mk(arch)
    cfg = cfg.replace(kv_quant=kv_quant)
    B, max_len, C, P = 2, 64, 16, 8
    decode, _ = server_programs(cfg, C)
    st = init_server_state(cfg, B, max_len, jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, P), 2,
                              cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(P)[None], (B, P))
    caches, nxt = make_prefill_step(cfg)(params, st["caches"], toks, pos)
    st = dict(st, caches=caches, length=jnp.full((B,), P, jnp.int32),
              last_token=nxt, active=jnp.array([False, True]))
    before = jax.tree.map(np.array, st["caches"])  # copies, not views
    new, _ = decode(params, st)
    assert jax.tree.leaves(st["caches"])[0].is_deleted()  # donated
    after = jax.tree.map(np.array, new["caches"])
    names = {p[-1].key for p, _ in jax.tree_util.tree_leaves_with_path(
        before)}
    if kv_quant:
        assert {"k", "v", "k_s", "v_s", "pos"} <= names
    for (path, b), a in zip(jax.tree_util.tree_leaves_with_path(before),
                            jax.tree.leaves(after)):
        # leaves are (layer, slot, ...): slot 0 is inactive, 1 active
        assert b[:, 0].tobytes() == a[:, 0].tobytes(), path
        assert b[:, 1].tobytes() != a[:, 1].tobytes(), path
    np.testing.assert_array_equal(np.asarray(new["length"]), [P, P + 1])


def test_kv_migration_preserves_tokens():
    """extract_slot/inject_slot must not change the decoded stream."""
    cfg, params = _mk()
    prim = ServicePrimitives(batch_cap=4, chunk=16)
    eng_a = ServerEngine(cfg, params, prim=prim, max_len=128)
    eng_b = ServerEngine(cfg, params, prim=prim, max_len=128)

    rng = np.random.default_rng(0)
    toks = rng.integers(2, cfg.vocab_size, size=24).astype(np.int32)
    req = SlotRequest(rid=0, cls=0, prompt_len=24, decode_len=6)
    eng_a.start_prefill(req, toks)
    while eng_a.has_prefill:
        eng_a.step()
    # migrate to engine B and decode there
    slot = next(i for i, s in enumerate(eng_a.slots) if s is req)
    _, sub, meta = eng_a.extract_slot(slot)
    eng_b.inject_slot(0, req, sub, meta)
    outs_b = []
    while req.tokens_out < req.decode_len:
        eng_b.step()
    outs_b = list(req.out_tokens)

    # reference: same request decoded without migration
    req2 = SlotRequest(rid=1, cls=0, prompt_len=24, decode_len=6)
    eng_c = ServerEngine(cfg, params, prim=prim, max_len=128)
    eng_c.start_prefill(req2, toks)
    while eng_c.has_prefill:
        eng_c.step()
    slot2 = next(i for i, s in enumerate(eng_c.slots) if s is req2)
    eng_c.activate_slot(slot2)
    while req2.tokens_out < req2.decode_len:
        eng_c.step()
    assert outs_b == req2.out_tokens


def test_real_cluster_end_to_end():
    cfg, params = _mk()
    prim = ServicePrimitives(batch_cap=4, chunk=16)
    pricing = Pricing()
    classes = [WorkloadClass("a", 24, 6, 0.5, 0.1),
               WorkloadClass("b", 8, 12, 0.5, 0.1)]
    plan = solve_bundled_lp(classes, prim, pricing)
    cl = RealCluster(cfg, params, classes, plan, prim, pricing,
                     n_servers=2, max_len=128)
    rng = np.random.default_rng(1)
    reqs, t = [], 0.0
    for k in range(6):
        t += rng.exponential(0.5)
        c = k % 2
        P = classes[c].prompt_len
        reqs.append((t, c, rng.integers(2, cfg.vocab_size, size=P)
                     .astype(np.int32), classes[c].decode_len))
    m = cl.run(reqs, horizon=500.0)
    assert m.completions == 6
    assert m.revenue > 0


def test_served_tokens_match_greedy_generate():
    """Chunked prefill, slot decode and KV migration through RealCluster
    give the same greedy tokens as a plain prefill-then-decode loop."""
    cfg, params = _mk()
    prim = ServicePrimitives(batch_cap=4, chunk=16)
    pricing = Pricing()
    classes = [WorkloadClass("a", 40, 6, 0.5, 0.1),
               WorkloadClass("b", 20, 9, 0.5, 0.1)]
    plan = solve_bundled_lp(classes, prim, pricing)
    cl = RealCluster(cfg, params, classes, plan, prim, pricing,
                     n_servers=2, max_len=128)
    rng = np.random.default_rng(3)
    # ragged prompts: a full chunk, a padded last chunk, a sub-chunk prompt
    reqs = [(0.01 * k, k % 2, rng.integers(2, cfg.vocab_size, size=P)
             .astype(np.int32), classes[k % 2].decode_len)
            for k, P in enumerate((32, 37, 9))]
    cl.run(reqs, horizon=500.0)
    assert sorted(r.rid for r in cl.completed) == [0, 1, 2]
    for req in cl.completed:
        _, _, toks, D = reqs[req.rid]
        assert req.out_tokens == M.greedy_generate(cfg, params, toks, D,
                                                   max_len=128)
