"""Integration tests for the real-compute serving path."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.planning import solve_bundled_lp
from repro.core.types import Pricing, ServicePrimitives, WorkloadClass
from repro.models import model as M
from repro.serving.cluster import RealCluster
from repro.serving.engine import ServerEngine, SlotRequest
from repro.serving.steps import (init_server_state, make_decode_step,
                                 make_mixed_step)


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
        from repro.serving.steps import make_prefill_step
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
