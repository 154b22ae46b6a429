"""The harness on the CPU at the tiny qwen2 shapes: counts, refusals,
cells found by name, the control and the faults that ``correct`` catches."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import harness
from bench.stats import percentile, weighted_percentile
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SEED = 3_000_000_019  # wider than 32 bits, as the check's seeds are


def _run(root, seconds=1.5, cell=tiny.CELL, seed=SEED):
    return harness.run(root, cell, seed, seconds, False, require_tpu=False)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Keep the test process's JAX cache settings as they are."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(tmp_path)


def test_tiny_cell_runs_and_is_correct(root):
    out = _run(root)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks"
    m = out["metrics"]
    assert set(m) == {"served_tok_s", "tpot_p95_ms", "ttft_p90_ms",
                      "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    gap = out["checks"]["max_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]


def test_counts_follow_their_definitions():
    steps = [  # sid, mixed, t0, t1, n_dec, sum_dec, produced, first, done, n
        (0, True, 0.0, 0.010, 0, 0, 0, 0, 0, 32),
        (0, True, 0.010, 0.030, 1, 50, 2, 1, 32, 10),
        (1, False, 0.030, 0.035, 3, 90, 3, 0, 0, 0),
    ]
    win = {"window_s": 0.5, "tokens": 5, "steps": steps,
           "tpot": [(0.020, 1), (0.005, 3)], "ttft_s": [0.030],
           "extract_s": [0.001], "inject_s": [0.002]}
    rec = {"window": win, "setup_s": 1.0, "trace": None, "peaks": None,
           "config": {}, "notes": []}
    read = lambda name: harness.read_metric(ROOT, name, rec)
    assert read("served_tok_s") == 10.0
    assert read("tpot_p95_ms") == pytest.approx(
        1e3 * percentile([0.020, 0.005, 0.005, 0.005], 95))
    assert read("ttft_p90_ms") == pytest.approx(30.0)
    assert read("handoff_ms") == pytest.approx(3.0)
    assert read("decode_slots_mean") == pytest.approx(4 / 3)
    assert read("cluster_host_share") == pytest.approx(
        100 * (1 - (0.035 + 0.003) / 0.5))
    assert read("tau_solo_ms") is None and read("served_mfu") is None
    assert weighted_percentile([], 95) is None


def test_window_counts_add_up(root, monkeypatch):
    seen = {}
    real = harness.read_metric

    def spy(r, name, rec):
        seen["window"] = rec["window"]
        return real(r, name, rec)

    monkeypatch.setattr(harness, "read_metric", spy)
    _run(root)
    w = seen["window"]
    firsts = sum(s[7] for s in w["steps"])
    assert w["tokens"] == sum(s[6] for s in w["steps"]) > 0
    assert sum(k for _, k in w["tpot"]) == w["tokens"] - firsts
    assert 0 < len(w["ttft_s"]) <= firsts
    assert len(w["extract_s"]) > 0
    n = w["samples"]
    assert (n["tokens"], n["steps"], n["tpot_tokens"], n["ttft_requests"],
            n["arrived"]) == (w["tokens"], len(w["steps"]),
                              w["tokens"] - firsts, len(w["ttft_s"]),
                              w["arrived"])


def test_check_reads_every_finished_request_in_every_slot_used():
    from bench.drivers.served import ServedCell

    c = json.loads((tiny.DATA / "qwen2-tiny.json").read_text())
    mix = json.loads((tiny.DATA / "tiny_mix.json").read_text())
    cell = ServedCell(c, mix, SEED, harness.Spans())
    cell.setup()
    win = cell.window(1.5)
    cell.release()
    got = cell.check()
    n = got["samples"]
    assert n["requests"] == win["samples"]["finished"] > 1
    assert n["tokens"] == sum(len(r.out_tokens) for r in cell.finished)
    assert n["slots_checked"] > 1 and n["slots_used"] >= n["slots_checked"]
    assert got["failed"] == 0


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen05b.azure_mixed", "--seed", "1", "--seconds",
                        "1"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "{" not in p.stdout


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen05b.azure_mixed", "--seed", "1", "--seconds",
                        "1"], cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


def test_new_cell_traffic_and_metric_found_by_name(root):
    shutil.copy(tiny.DATA / "tiny_mix.json",
                root / "bench" / "traffic" / "tiny_mix2.json")
    (root / "bench" / "metrics" / "windows_seen.py").write_text(
        "def read(rec):\n    return 1.0 + rec['window']['window_s'] * 0\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.mix2", "config": "qwen2-tiny",
                              "traffic": "tiny_mix2", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "windows_seen", "unit": "count",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["tiny.mix2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run(root, seconds=0.5, cell="tiny.mix2")
    assert out["metrics"]["windows_seen"]["value"] == 1.0
    assert harness.cell_metrics(spec, tiny.CELL, False)[-1]["name"] == (
        "setup_s")


def test_control_is_not_correct():
    """The float8 control put in the program's place reads over the limit
    on every seed, where the program reads under it."""
    from bench.drivers.served import ServedCell

    c = json.loads((tiny.DATA / "qwen2-tiny.json").read_text())
    mix = json.loads((tiny.DATA / "tiny_mix.json").read_text())
    limit = c["correct"]["limit"]
    for seed in (1, 2, 3):
        cell = ServedCell(c, mix, seed, harness.Spans())
        cell.setup()
        cell.window(1.0)
        cell.release()
        seqs = [(cell.prompts[r.rid], r.out_tokens) for r in cell.sample()]
        got = cell.ref.max_logit_gap(c, cell.params, seqs, max_len=256,
                                     control=True)
        assert got["max_logit_gap"] <= limit < got["control_gap"], got


def _state_unchanged(make):
    def build(cfg, **kw):
        step = make(cfg, **kw)

        def decode_step(params, state):
            _, tok = step(params, state)
            return state, tok
        return decode_step
    return build


def _half_batch(make):
    def build(cfg, **kw):
        step = make(cfg, **kw)

        def decode_step(params, state):
            act = state["active"]
            keep = jnp.arange(act.shape[0]) < act.shape[0] // 2
            new, tok = step(params, dict(state, active=act & keep))
            return dict(new, active=act), tok
        return decode_step
    return build


def _token_altered(make):
    def build(cfg, **kw):
        step = make(cfg, **kw)

        def decode_step(params, state):
            new, tok = step(params, state)
            tok = (tok + 1) % cfg.vocab_size
            return dict(new, last_token=jnp.where(
                state["active"], tok, state["last_token"])), tok
        return decode_step
    return build


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_fault_in_timed_path_is_not_correct(root, monkeypatch, fault):
    import repro.serving.engine as engine

    monkeypatch.setattr(engine, "make_decode_step",
                        fault(engine.make_decode_step))
    out = _run(root)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > (
        out["checks"]["max_logit_gap"]["limit"])
