"""Host spans of the served path: recorded only while the JAX profiler
records, nested as the calls are, counting what they say they count, and
never changing a token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.planning import solve_bundled_lp
from repro.core.types import Pricing, ServicePrimitives, WorkloadClass
from repro.models import model as M
from repro.serving.cluster import RealCluster
from repro.telemetry import spans

PROMPTS = (32, 37, 9, 50, 17, 24)   # full, padded and sub-chunk last chunks
MAX_LEN = 128


def _profiler(path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(path), profiler_options=opts)


@pytest.fixture
def clean():
    spans.clear()
    yield
    spans.clear()


def _serve(cfg, params):
    """A fresh two-server fleet run to completion on a fixed stream."""
    prim = ServicePrimitives(batch_cap=4, chunk=16)
    classes = [WorkloadClass("a", 40, 6, 0.5, 0.1),
               WorkloadClass("b", 20, 9, 0.5, 0.1)]
    plan = solve_bundled_lp(classes, prim, Pricing())
    cl = RealCluster(cfg, params, classes, plan, prim, Pricing(),
                     n_servers=2, max_len=MAX_LEN)
    rng = np.random.default_rng(3)
    reqs = [(0.01 * k, k % 2, rng.integers(2, cfg.vocab_size, size=P)
             .astype(np.int32), classes[k % 2].decode_len)
            for k, P in enumerate(PROMPTS)]
    cl.run(reqs, horizon=500.0)
    assert len(cl.completed) == len(PROMPTS)
    return cl, {r.rid: list(r.out_tokens) for r in cl.completed}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The same stream served with the profiler off, then on."""
    cfg = get_config("qwen2-0.5b", reduced=True)
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    spans.clear()
    _, off = _serve(cfg, params)
    n_off = len(spans.records())
    with _profiler(tmp_path_factory.mktemp("trace")):
        cl, on = _serve(cfg, params)
    recs = [list(r) for r in spans.records()]
    spans.clear()
    return {"cluster": cl, "off": off, "on": on,
            "n_off": n_off, "recs": recs}


def _named(recs, name):
    return [(i, r) for i, r in enumerate(recs) if r[0] == name]


def _children(recs, i):
    return [r[0] for r in recs if r[3] == i]


def test_nothing_is_recorded_without_a_profiler(clean):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with spans.span("x", server=1) as args:
        spans.add("k")
    assert args is None
    assert spans.span("y") is spans.span("z")
    assert spans.records() == [] and spans.dropped() == 0


def test_served_run_records_nothing_without_a_profiler(served):
    assert served["n_off"] == 0


def test_recorder_nests_and_counts_inclusively(clean, tmp_path):
    with _profiler(tmp_path):
        with spans.span("outer", server=2) as outer:
            spans.add("k")
            with spans.span("inner") as inner:
                inner["seen"] = True
                spans.add("k", 2)
        with spans.span("next"):
            pass
    recs = spans.records()
    assert [r[0] for r in recs] == ["outer", "inner", "next"]
    assert [r[3] for r in recs] == [-1, 0, -1]
    assert recs[0][4] == outer == {"server": 2, "k": 3}
    assert recs[1][4] == {"seen": True, "k": 2}
    assert all(r[1] <= r[2] for r in recs)
    assert recs[0][1] <= recs[1][1] <= recs[1][2] <= recs[0][2]


def test_records_are_bounded(clean, tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "MAX_RECORDS", 2)
    with _profiler(tmp_path):
        for name in "abc":
            with spans.span(name):
                spans.add("k")
    assert [r[0] for r in spans.records()] == ["a", "b"]
    assert spans.dropped() == 1


def test_compiles_are_charged_to_the_span_that_compiled(clean, tmp_path):
    f = jax.jit(lambda x: x * 3 + 1)
    with _profiler(tmp_path):
        with spans.span("outer"):
            with spans.span("first"):
                f(jnp.ones(5))
            with spans.span("again"):
                f(jnp.ones(5))
    outer, first, again = spans.records()
    assert first[4]["compiles"] >= 1
    assert "compiles" not in again[4]
    assert outer[4]["compiles"] == first[4]["compiles"]


def test_served_compiles_land_in_the_first_launch(served):
    recs = served["recs"]
    launches = _named(recs, "serve.engine.launch")
    assert launches[0][1][4].get("compiles", 0) >= 1
    i, step = _named(recs, "serve.engine.step")[0]
    assert launches[0][1][3] == i
    assert step[4]["compiles"] >= launches[0][1][4]["compiles"]
    run = _named(recs, "serve.cluster.run")[0][1]
    assert run[4]["compiles"] >= step[4]["compiles"]


def test_token_streams_are_the_same_with_recording_on_and_off(served):
    assert served["on"] == served["off"]


def test_served_spans_nest_as_the_calls_do(served):
    recs = served["recs"]
    assert all(r[2] is not None and r[1] <= r[2] for r in recs)
    for r in recs:
        if r[3] >= 0:
            p = recs[r[3]]
            assert p[1] <= r[1] and r[2] <= p[2], (r, p)
    parent = {"serve.cluster.run": None,
              "serve.cluster.admit": "serve.cluster.run",
              "serve.cluster.route": "serve.cluster.run",
              "serve.engine.step": "serve.cluster.run",
              "serve.engine.extract": "serve.cluster.run",
              "serve.engine.inject": "serve.cluster.route",
              "serve.engine.launch": "serve.engine.step",
              "serve.engine.fetch": "serve.engine.step",
              "serve.engine.account": "serve.engine.step",
              "serve.kv.to_host": "serve.engine.extract",
              "serve.kv.to_device": "serve.engine.inject"}
    assert {r[0] for r in recs} == set(parent)
    for r in recs:
        want = parent[r[0]]
        assert (recs[r[3]][0] if r[3] >= 0 else None) == want, r
    for i, _ in _named(recs, "serve.engine.step"):
        assert _children(recs, i) == ["serve.engine.launch",
                                      "serve.engine.fetch",
                                      "serve.engine.account"]
    for i, _ in _named(recs, "serve.engine.extract"):
        assert _children(recs, i) == ["serve.kv.to_host"]
    for i, _ in _named(recs, "serve.engine.inject"):
        assert _children(recs, i) == ["serve.kv.to_device"]
    runs = _named(recs, "serve.cluster.run")
    assert len(runs) == 1 and runs[0][1][4]["events"] > len(PROMPTS)


def test_step_host_reads_are_the_fetch_and_one_per_decoding_slot(served):
    recs = served["recs"]
    steps = [r for _, r in _named(recs, "serve.engine.step")]
    last = {}
    for k, r in enumerate(steps):
        assert r[4]["server"] in (0, 1)
        if r[4]["kind"] == "mixed":
            last[r[4]["rid"]] = k
        else:
            assert r[4]["kind"] == "solo" and "rid" not in r[4]
    assert sorted(last) == list(range(len(PROMPTS)))
    last_chunk = set(last.values())
    for k, r in enumerate(steps):
        a = r[4]
        assert a["host_reads"] == 1 + a["slots"] + (k in last_chunk), a
        assert a["eager_ops"] >= a["slots"]
    assert any(r[4]["slots"] > 0 for r in steps)


def test_handoff_bytes_are_the_host_tree_and_its_live_part(served):
    recs, cl = served["recs"], served["cluster"]
    B = cl.prim.batch_cap
    slot_bytes = sum(a.nbytes for a in
                     jax.tree.leaves(cl.engines[0].state["caches"])) // B
    per_token = slot_bytes // MAX_LEN
    extracts = [r[4] for _, r in _named(recs, "serve.engine.extract")]
    injects = [r[4] for _, r in _named(recs, "serve.engine.inject")]
    assert extracts and len(injects) == len(extracts)
    prompt = {r.rid: r.prompt_len for r in cl.completed}
    for a in extracts:
        assert a["bytes"] == slot_bytes
        assert a["live_bytes"] == prompt[a["rid"]] * per_token
        assert a["host_reads"] == len(jax.tree.leaves(
            cl.engines[0].state["caches"])) + 2
    assert [a["rid"] for a in injects] == [a["rid"] for a in extracts]
    assert all(a["bytes"] == slot_bytes for a in injects)
