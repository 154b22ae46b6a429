"""The trace reduction, on intervals by hand and on a trace recorded on a
TPU v5e (``bench/testdata/served_tiny.xplane.pb``: the tiny test
configuration served through the driver, recorded by
``bench/tools/trace_probe.py``)."""

from pathlib import Path

import pytest

from bench import trace_reduce as tr

SYNC = ("bench.step", ("decode_step", "mixed_step"))
TRACE = Path(__file__).resolve().parents[1] / "testdata" / "served_tiny.xplane.pb"


def test_union_and_gaps_by_hand():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.7), (8, 12)]
    assert tr.union_length(iv) == 3 + 1 + 4
    assert tr.gaps(iv, 0, 10) == [(3, 5), (6, 8)]
    assert tr.gaps(iv, -1, 7) == [(-1, 0), (3, 5), (6, 7)]
    assert tr.union_length([]) == 0.0


def test_names():
    assert tr.program_name("jit_decode_step(16159278107012271113)") == (
        "decode_step")
    assert tr.program_name("mixed_step") == "mixed_step"
    assert tr.op_name("%fusion.12 = f32[64]{0} fusion(bf16[64] %p)") == (
        "fusion.12")


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(TRACE))


@pytest.fixture(scope="module")
def reduced(profile):
    return tr.reduce_profile(profile, n_devices=1, sync=SYNC)


def _host(profile, name):
    return [(e.start_ns, e.start_ns + e.duration_ns)
            for p in profile.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events if e.name == name]


def test_busy_and_idle_fill_the_window(reduced):
    idle = sum(v for _, v in reduced["breakdown"]["idle_gaps"])
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["busy_s"] + idle == pytest.approx(reduced["window_s"],
                                                     rel=1e-9)


def test_one_program_execution_per_step(profile, reduced):
    (lo, hi), = _host(profile, "bench.window")
    steps = [s for s in _host(profile, "bench.step") if lo <= s[0] < hi]
    progs = reduced["programs"]
    assert progs["decode_step"]["count"] + progs["mixed_step"]["count"] == (
        len(steps))
    assert all(p["device_s"] > 0 for p in progs.values())
    assert 0 < reduced["clock_offset_s"] < 0.01


def test_breakdown_names_programs_and_host_spans(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10
    assert any(n.startswith(("decode_step:", "mixed_step:")) for n, _ in ops)
    owners = {n for n, _ in reduced["breakdown"]["idle_gaps"]}
    assert "bench.step" in owners
    assert owners <= {"bench.step", "bench.slice", "bench.extract_slot",
                      "bench.inject_slot", "bench.start_prefill",
                      "outside bench spans"}


def test_more_devices_than_the_trace_holds_is_refused(profile):
    with pytest.raises(ValueError, match="TPU planes"):
        tr.reduce_profile(profile, n_devices=2)
