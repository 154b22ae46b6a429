"""SPMD sharding layer: plan/padding invariants, the unified evaluator
registry, and device-count invariance of the sharded runner.

The expensive guarantee -- ``placement="shard_map"`` bitwise-equal to
the single-device ``vmap`` oracle on a REAL multi-device mesh -- runs in
a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the flag must be set before jax imports), on a grid whose cell count
does not divide the mesh, so ragged padding/masking is exercised at the
same time.
"""

import json
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.sweep.sharded import (PLACEMENTS, ShardPlan, pad_batch,
                                 plan_shards, run_sharded)
from repro.sweep.spec import EVALUATORS, SweepSpec, get_evaluator

# ---------------------------------------------------------------------------
# plan_shards / ShardPlan invariants (manual property sweep; seeded)
# ---------------------------------------------------------------------------


def test_plan_shards_invariants():
    rng = np.random.default_rng(0)
    for _ in range(200):
        n_cells = int(rng.integers(1, 500))
        d = int(rng.integers(1, 17))
        cap = int(rng.integers(1, 64)) if rng.random() < 0.5 else None
        plan = plan_shards(n_cells, n_devices=d, max_cells_per_device=cap)
        # every cell is covered, in whole equal-shape tiles
        assert plan.padded >= n_cells
        assert plan.padded == plan.n_tiles * plan.tile
        assert plan.tile == plan.n_devices * plan.per_device
        assert plan.n_padding == plan.padded - n_cells
        assert plan.n_padding < plan.tile  # never a whole wasted tile
        if cap is not None:
            assert plan.per_device <= cap
        else:
            assert plan.n_tiles == 1  # uncapped: one pass
        r = plan.report()
        assert r["n_cells"] == n_cells and r["n_devices"] == d


def test_plan_shards_memory_budget():
    # cap derived from a per-cell footprint: floor(budget / bytes)
    plan = plan_shards(100, n_devices=4, bytes_per_cell=1000.0,
                       memory_budget=3500.0)
    assert plan.per_device == 3
    # explicit cap wins when tighter
    plan = plan_shards(100, n_devices=4, max_cells_per_device=2,
                       bytes_per_cell=1000.0, memory_budget=3500.0)
    assert plan.per_device == 2


def test_plan_shards_rejects_degenerate():
    with pytest.raises(ValueError):
        plan_shards(0, n_devices=2)
    with pytest.raises(ValueError):
        plan_shards(4, n_devices=2, max_cells_per_device=0)
    with pytest.raises(ValueError):
        plan_shards(4, n_devices=2, bytes_per_cell=-1.0, memory_budget=8.0)
    with pytest.raises(ValueError):
        ShardPlan(n_cells=4, n_devices=0, per_device=1)


def test_pad_batch_repeats_cell_zero():
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    for _ in range(20):
        n = int(rng.integers(1, 12))
        padded = n + int(rng.integers(0, 7))
        tree = {"a": jnp.asarray(rng.normal(size=(n, 3))),
                "b": jnp.asarray(rng.integers(0, 9, size=(n,)))}
        out = pad_batch(tree, padded)
        for k in tree:
            got = np.asarray(out[k])
            assert got.shape[0] == padded
            np.testing.assert_array_equal(got[:n], np.asarray(tree[k]))
            for j in range(n, padded):  # padding lanes repeat cell 0
                np.testing.assert_array_equal(got[j], got[0])


# ---------------------------------------------------------------------------
# the unified evaluator registry
# ---------------------------------------------------------------------------


def test_every_evaluator_name_registers():
    for name in EVALUATORS:
        ev = get_evaluator(name)
        assert ev.name == name
        assert callable(ev.fn)
    with pytest.raises(Exception):
        get_evaluator("no_such_evaluator")


def test_deterministic_flags_and_prepare_hooks():
    assert get_evaluator("lp").deterministic
    assert get_evaluator("fluid").deterministic
    assert get_evaluator("lp_jax").deterministic
    assert get_evaluator("fluid").prepare is not None
    assert get_evaluator("lp_jax").prepare is not None
    for name in ("ctmc", "ctmc_jax", "engine", "engine_jax"):
        assert not get_evaluator(name).deterministic


def test_deprecated_shims_warn_and_agree():
    from repro.sweep.evaluators import MixContext, evaluate_lp_cell
    from repro.sweep.run import default_mix

    spec = SweepSpec(name="t", evaluator="lp", policies=("lp",),
                     n_servers=(10,), mixes=(default_mix(),))
    ctx = MixContext(default_mix(), spec)
    with pytest.warns(DeprecationWarning):
        legacy = evaluate_lp_cell(ctx, "lp")
    cells = get_evaluator("lp")(ctx, "lp", 10, seeds=[None, None])
    assert len(cells) == 2  # deterministic dict replicated per seed
    assert cells[0].metrics == legacy


def test_get_evaluator_unknown_name_lists_known():
    """The dispatch error must name every registered evaluator, so a
    typo'd spec.evaluator is self-diagnosing."""
    with pytest.raises(Exception, match="no evaluator registered") as exc:
        get_evaluator("no_such_evaluator")
    msg = str(exc.value)
    for name in EVALUATORS:
        assert name in msg, f"{name} missing from: {msg}"


def test_all_deprecated_shims_warn_and_agree():
    """Every legacy ``evaluate_*`` entry point must (a) emit a
    DeprecationWarning pointing at ``get_evaluator`` and (b) return
    results identical to the registered Evaluator it wraps."""
    from repro.sweep.evaluators import (MixContext, evaluate_ctmc_cells,
                                        evaluate_ctmc_jax_cells,
                                        evaluate_engine_cell,
                                        evaluate_engine_jax_cells,
                                        evaluate_lp_cell,
                                        evaluate_lp_jax_grid)
    from repro.sweep.run import default_mix
    from repro.sweep.spec import cell_seed_sequence

    mix = default_mix("two_class")
    spec = SweepSpec(name="t", evaluator="ctmc",
                     policies=("gate_and_route",), n_servers=(4,),
                     n_seeds=2, seed=7, mixes=(mix,),
                     horizon=6.0, warmup=1.0)
    n = 4
    token = "gate_and_route"
    streams = [cell_seed_sequence(spec, 0, 0, 0, s) for s in range(2)]

    def fresh_ctx():
        return MixContext(mix, spec)

    # seed-replicated stochastic shims: (shim, registered name)
    for shim, name in ((evaluate_ctmc_cells, "ctmc"),
                       (evaluate_ctmc_jax_cells, "ctmc_jax"),
                       (evaluate_engine_jax_cells, "engine_jax")):
        with pytest.warns(DeprecationWarning, match="get_evaluator"):
            legacy = shim(fresh_ctx(), token, n, streams)
        cells = get_evaluator(name)(fresh_ctx(), token, n, seeds=streams)
        assert len(legacy) == len(cells) == 2
        for old, new in zip(legacy, cells):
            assert dict(old) == new.metrics, name

    # single-seed Python trace engine shim
    with pytest.warns(DeprecationWarning, match="get_evaluator"):
        legacy = evaluate_engine_cell(fresh_ctx(), token, n, streams[0])
    (cell,) = get_evaluator("engine")(fresh_ctx(), token, n,
                                      seeds=streams[:1])
    assert dict(legacy) == cell.metrics

    # deterministic planners: no seed axis
    with pytest.warns(DeprecationWarning, match="get_evaluator"):
        legacy = evaluate_lp_cell(fresh_ctx(), "lp")
    (cell,) = get_evaluator("lp")(fresh_ctx(), "lp", n, seeds=[None])
    assert legacy == cell.metrics

    ctx = fresh_ctx()
    with pytest.warns(DeprecationWarning, match="get_evaluator"):
        grid = evaluate_lp_jax_grid([ctx], ["lp"])
    (cell,) = get_evaluator("lp_jax")(fresh_ctx(), "lp", n, seeds=[None])
    assert grid[(0, 0)] == cell.metrics


def test_run_sweep_rejects_unknown_placement():
    from repro.sweep import run_sweep
    from repro.sweep.run import default_mix

    spec = SweepSpec(name="t", evaluator="lp", policies=("lp",),
                     n_servers=(10,), mixes=(default_mix(),),
                     extra={"placement": "warp_drive"})
    with pytest.raises(ValueError, match="placement"):
        run_sweep(spec)


# ---------------------------------------------------------------------------
# sharded runner vs the vmap oracle (1 device in-process, 8 forced in a
# subprocess)
# ---------------------------------------------------------------------------


def _toy_kernel_case(n_cells):
    import jax
    import jax.numpy as jnp

    def kernel(rep, item):
        key, x = item
        noise = jax.random.normal(key, x.shape)
        return {"y": jnp.cumsum(rep["w"] * x + noise),
                "s": jnp.sum(x) + rep["b"]}

    rep = {"w": jnp.asarray(1.5), "b": jnp.asarray(-0.25)}
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n_cells))
    xs = jnp.linspace(0.0, 1.0, n_cells * 4).reshape(n_cells, 4)
    return kernel, rep, (keys, xs)


@pytest.mark.sim
def test_run_sharded_matches_vmap_one_device():
    import jax
    from repro.sweep.sharded import reset_warn_once

    kernel, rep, batched = _toy_kernel_case(5)
    # the oracle is the JITTED vmap -- what the engines actually run
    # (eager vmap may fuse float math differently; bitwise claims are
    # always jit-vs-jit)
    oracle = jax.jit(jax.vmap(lambda k, x: kernel(rep, (k, x))))(*batched)

    reset_warn_once("shard-serial")
    with pytest.warns(RuntimeWarning, match="1-device mesh"):
        raw, report = run_sharded(kernel, rep, batched, n_devices=1)
    assert report["serialized"] and report["n_devices"] == 1
    for k in ("y", "s"):
        np.testing.assert_array_equal(np.asarray(raw[k]),
                                      np.asarray(oracle[k]))

    # the per-process dedupe: the "shard-serial" kind is spent, so a
    # second serialized run stays quiet instead of warning once per call
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        run_sharded(kernel, rep, batched, n_devices=1)
    assert not [x for x in w if issubclass(x.category, RuntimeWarning)
                and "1-device mesh" in str(x.message)]


@pytest.mark.sim
def test_run_sharded_tiling_matches_vmap():
    import jax

    # 7 cells, cap 2 per device -> multiple tiles + ragged padding
    kernel, rep, batched = _toy_kernel_case(7)
    oracle = jax.jit(jax.vmap(lambda k, x: kernel(rep, (k, x))))(*batched)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        raw, report = run_sharded(kernel, rep, batched, n_devices=1,
                                  max_cells_per_device=2)
    assert report["n_tiles"] == 4 and report["n_padding"] == 1
    for k in ("y", "s"):
        np.testing.assert_array_equal(np.asarray(raw[k]),
                                      np.asarray(oracle[k]))


@pytest.mark.sim
def test_ctmc_jax_x64_extra():
    # extra["ctmc_jax"]["x64"] scopes the whole cell in double precision
    # (the gap study needs it: the float32 clock stalls at production n)
    import jax
    import jax.numpy as jnp

    from repro.core.ctmc_jax import UniformizedCTMC
    from repro.sweep.evaluators import MixContext, resolve_policy
    from repro.sweep.run import default_mix
    from repro.sweep.spec import cell_seed_sequence

    spec = SweepSpec(name="t", evaluator="ctmc_jax",
                     policies=("gate_and_route",), n_servers=(10,),
                     n_seeds=2, mixes=(default_mix(),), horizon=3.0,
                     warmup=1.0, extra={"ctmc_jax": {"x64": True}})
    ctx = MixContext(default_mix(), spec)
    with jax.enable_x64(True):
        sim = UniformizedCTMC(ctx.classes, ctx.prim, ctx.pricing,
                              resolve_policy("gate_and_route", ctx, 10),
                              n=10, horizon=3.0, warmup=1.0)
        assert sim.params["lam_tot"].dtype == jnp.float64
    streams = [cell_seed_sequence(spec, 0, 0, 0, si) for si in range(2)]
    cells = get_evaluator("ctmc_jax")(ctx, "gate_and_route", 10,
                                      seeds=streams)
    assert all(c.metrics["t_end"] == 3.0 for c in cells)
    assert all(np.isfinite(c.metrics["revenue_rate"]) for c in cells)


@pytest.mark.sim
def test_engine_jax_facade_placements_agree():
    from repro.sweep.evaluators import MixContext
    from repro.sweep.spec import MixSpec, cell_seed_sequence

    mix = MixSpec(name="tr", trace=dict(horizon=3.0, seed=1,
                                        compression=0.02))
    spec = SweepSpec(name="t", evaluator="engine_jax", policies=("vllm",),
                     n_servers=(8,), n_seeds=4, mixes=(mix,),
                     horizon=3.0, warmup=0.5)
    ctx = MixContext(mix, spec)
    streams = [cell_seed_sequence(spec, 0, 0, 0, si) for si in range(4)]
    ev = get_evaluator("engine_jax")
    ref = ev(ctx, "vllm", 8, seeds=streams, placement="vmap")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        shd = ev(ctx, "vllm", 8, seeds=streams, placement="shard_map")
    assert [c.metrics for c in shd] == [c.metrics for c in ref]


# the full device-count-invariance guarantee: 8 forced host devices, a
# 5-cell grid (ragged on the mesh), bitwise equality with the oracle
SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, warnings
import jax
assert jax.device_count() == 8, jax.devices()
from repro.sweep import SweepSpec, run_sweep
from repro.sweep.run import default_mix

spec = SweepSpec(name="t", evaluator="ctmc_jax",
                 policies=("gate_and_route",), n_servers=(10,), n_seeds=5,
                 mixes=(default_mix(),), horizon=3.0, warmup=1.0,
                 extra={"placement": "shard_map"})
with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    res = run_sweep(spec)
assert res.meta["shard_devices"] == 8, res.meta
print("CELLS=" + json.dumps([c.metrics for c in res.cells]))
"""


@pytest.mark.sim
def test_shard_map_eight_devices_matches_vmap_oracle():
    from repro.sweep import run_sweep
    from repro.sweep.run import default_mix

    spec = SweepSpec(name="t", evaluator="ctmc_jax",
                     policies=("gate_and_route",), n_servers=(10,),
                     n_seeds=5, mixes=(default_mix(),), horizon=3.0,
                     warmup=1.0, extra={"placement": "vmap"})
    oracle = run_sweep(spec)

    r = subprocess.run([sys.executable, "-c", SUBPROC], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert "CELLS=" in r.stdout, r.stdout + r.stderr
    line = next(l for l in r.stdout.splitlines() if l.startswith("CELLS="))
    sharded_metrics = json.loads(line[len("CELLS="):])
    assert sharded_metrics == [c.metrics for c in oracle.cells]
