"""The readers of the program's own spans: values on hand-made records,
nothing where nothing was recorded, and every one of them on a tiny cell
served with the profiler on."""

import json
import sys
from pathlib import Path

import jax
import pytest

from bench import harness
from bench.tests import tiny
from repro.telemetry import spans

ROOT = Path(__file__).resolve().parents[2]
READERS = ("account_ms", "host_reads_per_step", "kv_to_host_ms",
           "handoff_live_share", "gate_route_us")

RECS = [
    ["serve.cluster.run", 0.0, 1.0, -1, {"events": 10}],
    ["serve.cluster.admit", 0.0, 0.01, 0, {}],
    ["serve.engine.step", 0.10, 0.14, 0,
     {"server": 0, "kind": "solo", "slots": 4, "host_reads": 5}],
    ["serve.engine.launch", 0.10, 0.11, 2, {}],
    ["serve.engine.fetch", 0.11, 0.12, 2, {"host_reads": 1}],
    ["serve.engine.account", 0.12, 0.14, 2, {"host_reads": 4}],
    ["serve.engine.extract", 0.20, 0.35, 0,
     {"server": 0, "rid": 7, "bytes": 1000, "live_bytes": 250}],
    ["serve.kv.to_host", 0.20, 0.30, 6, {}],
    ["serve.cluster.route", 0.35, 0.50, 0, {}],
    ["serve.engine.inject", 0.36, 0.46, 8, {"server": 1, "rid": 7,
                                            "bytes": 1000}],
    ["serve.kv.to_device", 0.37, 0.45, 9, {}],
    ["serve.engine.step", 0.50, 0.52, 0,
     {"server": 1, "kind": "solo", "slots": 1, "host_reads": 2}],
    ["serve.engine.account", 0.51, 0.52, 11, {}],
    ["serve.engine.extract", 0.60, 0.70, 0,
     {"server": 0, "rid": 8, "bytes": 1000, "live_bytes": 750}],
    ["serve.kv.to_host", 0.60, 0.65, 13, {}],
]

EXPECTED = {
    "account_ms": 15.0,           # (20 + 10) ms over two steps
    "host_reads_per_step": 3.5,   # (5 + 2) over two steps
    "kv_to_host_ms": 75.0,        # (100 + 50) ms over two extracts
    "handoff_live_share": 50.0,   # 1000 of 2000 bytes
    "gate_route_us": 6000.0,      # admit 10 ms + route 150 - 100 ms, 10 events
}


def _read(name):
    return harness.read_metric(ROOT, name, {})


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_made_records(name, monkeypatch):
    monkeypatch.setattr(spans, "records", lambda: RECS)
    assert _read(name) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_where_nothing_was_recorded(name, monkeypatch):
    monkeypatch.setattr(spans, "records", lambda: [])
    assert _read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_program_without_spans(name,
                                                          monkeypatch):
    import repro.telemetry

    monkeypatch.delattr(repro.telemetry, "spans")
    monkeypatch.setitem(sys.modules, "repro.telemetry.spans", None)
    assert _read(name) is None


def test_readers_on_a_tiny_cell_served_under_the_profiler(tmp_path):
    from bench.drivers.served import ServedCell

    c = json.loads((tiny.DATA / "qwen2-tiny.json").read_text())
    mix = json.loads((tiny.DATA / "tiny_mix.json").read_text())
    cell = ServedCell(c, mix, 5, harness.Spans())
    cell.setup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    spans.clear()
    try:
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            win = cell.window(1.5)
        rec = {"window": win}
        got = {n: harness.read_metric(ROOT, n, rec) for n in READERS}
        slots = harness.read_metric(ROOT, "decode_slots_mean", rec)
        n_steps = sum(r[0] == "serve.engine.step" for r in spans.records())
    finally:
        spans.clear()
        cell.release()
    assert all(v is not None and v > 0 for v in got.values()), got
    assert n_steps == len(win["steps"])
    assert 0 <= got["host_reads_per_step"] - slots - 1 < 1
    assert 0 < got["handoff_live_share"] < 100
