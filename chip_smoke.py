#!/usr/bin/env python3
"""Run the served and evaluation paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: served, evaluation, kernels
    python chip_smoke.py --chips 4   # only the sharded sweep, on four chips

Every phase runs in this one process, which holds the chip.  Each phase
prints its results, then its wall and compile seconds, on lines of its
own.  If any phase fails, or JAX finds no TPU, the script exits non-zero
and prints no result line; otherwise its last line is one JSON object
naming the device.  Weights are random from ``--seed``; nothing is read
from outside the repository and nothing is downloaded.  JAX's persistent
compilation cache is on (``repro.launch.compile_cache``).

Phases (one chip):

* ``served``: ``repro.launch.serve.serve`` on the published qwen2-0.5b
  config in bf16 -- 4 servers, 16 slots each, 256-token chunks, 2048-token
  slots, 16 requests.  Every request must finish with its full decode
  length of token ids in ``[0, vocab)``.
* ``replay``: one request served through the same path in float32 under
  ``highest`` matmul precision must give the same greedy tokens as a
  plain prefill-then-decode loop.  Float32, because random bf16 weights
  leave near-tied logits that a different summation order flips.
* ``evaluation``: ``repro.sweep.run.main`` with the ``ctmc_jax``,
  ``engine_jax`` and ``lp_jax`` evaluators on small grids, holding each
  to its diagnostics.
* ``kernels``: the calibration ``kernels`` backend on the tiny grid for
  qwen2-0.5b, with the Pallas kernels compiled for the chip.

``--chips 4`` runs only ``sharded``: a ``ctmc_jax`` and an ``engine_jax``
grid with ``placement="shard_map"`` over every device, each compared
bitwise with ``placement="vmap"`` on one device.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen2-0.5b"


# ----------------------------------------------------------------- phases
def phase_served(cfg, *, servers, requests, batch_cap, chunk, max_len,
                 seed=0) -> dict:
    from repro.launch.serve import serve

    cluster, reqs, metrics = serve(
        cfg, servers=servers, requests=requests, batch_cap=batch_cap,
        chunk=chunk, max_len=max_len, seed=seed)
    done = sorted(cluster.completed, key=lambda r: r.rid)
    if [r.rid for r in done] != list(range(len(reqs))):
        raise AssertionError(
            f"{len(done)} of {len(reqs)} requests completed")
    for req, (_, _, _, D) in zip(done, reqs):
        toks = req.out_tokens
        if req.tokens_out != D or len(toks) != D:
            raise AssertionError(
                f"request {req.rid}: {len(toks)} tokens, decode_len {D}")
        if min(toks) < 0 or max(toks) >= cfg.vocab_size:
            raise AssertionError(f"request {req.rid}: token id out of "
                                 f"[0, {cfg.vocab_size}): {toks}")
    return {
        "requests_completed": len(done),
        "tokens_out": sum(len(r.out_tokens) for r in done),
        "prompt_tokens": sum(len(t) for _, _, t, _ in reqs),
        "kv_migrations": metrics.migrations,
    }


def phase_replay(cfg, *, batch_cap, chunk, max_len, seed=0) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import serve
    from repro.models import model as M

    cfg32 = cfg.replace(param_dtype="float32")
    with jax.default_matmul_precision("highest"):
        params = M.init_model(cfg32, jax.random.PRNGKey(seed), jnp.float32)
        cluster, reqs, _ = serve(cfg32, servers=2, requests=1,
                                 batch_cap=batch_cap, chunk=chunk,
                                 max_len=max_len, seed=seed, params=params)
        (_, _, prompt, D), = reqs
        (req,) = cluster.completed
        ref = M.greedy_generate(cfg32, params, prompt, D, max_len=max_len)
    if req.out_tokens != ref:
        raise AssertionError(f"served {req.out_tokens} != plain {ref}")
    return {"prompt_len": len(prompt), "tokens_matched": len(ref)}


EVAL_GRIDS = {
    "ctmc_jax": ["--policies", "gate_and_route", "--ns", "10,20",
                 "--n-seeds", "2", "--horizon", "5", "--warmup", "1"],
    "engine_jax": ["--scenarios", "azure_2023",
                   "--policies", "gate_and_route,vllm", "--ns", "4",
                   "--n-seeds", "2", "--horizon", "20", "--warmup", "0"],
    "lp_jax": ["--policies", "lp,lp_separate,lp_sli", "--ns", "10",
               "--n-seeds", "1"],
}


def _check_cell(evaluator: str, m: dict, horizon: float) -> None:
    if evaluator == "ctmc_jax":
        ok = m["t_end"] == horizon and m["clip_steps"] == 0
    elif evaluator == "engine_jax":
        ok = m["budget_exhausted"] == 0 and m["n_dropped"] == 0
    else:
        ok = m["lp_converged"] == 1.0
    if not ok:
        raise AssertionError(f"{evaluator} cell fails its diagnostics: {m}")


def phase_evaluation() -> dict:
    from repro.core.lp_jax import solve_device
    from repro.sweep.run import main as sweep_main

    out = {"lp_jax_device": str(solve_device())}
    with tempfile.TemporaryDirectory() as tmp:
        for evaluator, grid in EVAL_GRIDS.items():
            path = Path(tmp) / f"{evaluator}.json"
            rc = sweep_main(["--evaluator", evaluator, *grid,
                             "--name", f"smoke-{evaluator}",
                             "--out", str(path)])
            if rc != 0:
                raise AssertionError(f"{evaluator} sweep exited {rc}")
            payload = json.loads(path.read_text())
            horizon = float(payload["spec"]["horizon"])
            cells = payload["cells"]
            for cell in cells:
                _check_cell(evaluator, cell["metrics"], horizon)
            out[f"{evaluator}_cells"] = len(cells)
    return out


def phase_kernels(*, reps: int, reduced: bool = False) -> dict:
    from repro.calibration import CalibrationGrid
    from repro.calibration.run import calibrate

    art = calibrate(ARCH, grid=CalibrationGrid.tiny(), backend="kernels",
                    reps=reps, reduced=reduced)
    taus = [s.tau for s in art.samples]
    if art.backend != "kernels" or not all(
            math.isfinite(t) and t > 0 for t in taus):
        raise AssertionError(f"bad kernel samples: {art.to_dict()}")
    return {"cells": len(taus), "alpha": art.alpha, "beta": art.beta,
            "a_s": art.a_s, "b_s": art.b_s,
            "r2_mix": art.mix.r2, "r2_solo": art.solo.r2}


def kernels_are_compiled(cfg) -> dict:
    """Lower both attention kernels at a calibration shape and require a
    Mosaic custom call (a kernel in interpret mode lowers to plain HLO)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.prefill_attention.ops import prefill_attention

    H, KV, D = cfg.attn.n_heads, cfg.attn.n_kv_heads, cfg.attn.head_dim
    bf = jnp.bfloat16
    dec = decode_attention.lower(
        jax.ShapeDtypeStruct((8, 1, H, D), bf),
        jax.ShapeDtypeStruct((8, 100, KV, D), bf),
        jax.ShapeDtypeStruct((8, 100, KV, D), bf),
        jax.ShapeDtypeStruct((8,), jnp.int32)).as_text()
    pre = prefill_attention.lower(
        jax.ShapeDtypeStruct((1, 100, H, D), bf),
        jax.ShapeDtypeStruct((1, 100, KV, D), bf),
        jax.ShapeDtypeStruct((1, 100, KV, D), bf)).as_text()
    found = {"decode": "tpu_custom_call" in dec,
             "prefill": "tpu_custom_call" in pre}
    if not all(found.values()):
        raise AssertionError(f"kernel lowered without Mosaic: {found}")
    return {"mosaic_custom_call": found}


def phase_sharded() -> dict:
    """shard_map over every device vs vmap on one, bitwise, per grid."""
    import dataclasses
    import warnings

    import jax

    from repro.sweep import MixSpec, SweepSpec, run_sweep
    from repro.sweep.run import default_mix

    n_dev = jax.device_count()
    specs = [
        SweepSpec(name="ctmc", evaluator="ctmc_jax",
                  policies=("gate_and_route",), n_servers=(10,),
                  n_seeds=10, mixes=(default_mix(),), horizon=3.0,
                  warmup=1.0),
        SweepSpec(name="engine", evaluator="engine_jax",
                  policies=("vllm",), n_servers=(8,), n_seeds=6,
                  mixes=(MixSpec(name="tr", trace=dict(
                      horizon=3.0, seed=1, compression=0.02)),),
                  horizon=3.0, warmup=0.5),
    ]

    def same(a: float, b: float) -> bool:
        return a == b or (math.isnan(a) and math.isnan(b))

    out = {"devices": n_dev}
    for spec in specs:
        ref = run_sweep(dataclasses.replace(spec,
                                            extra={"placement": "vmap"}))
        with warnings.catch_warnings():  # the 1-device serial warning
            warnings.simplefilter("ignore")
            shd = run_sweep(dataclasses.replace(
                spec, extra={"placement": "shard_map"}))
        if shd.meta["shard_devices"] != n_dev:
            raise AssertionError(f"{spec.name}: sharded over "
                                 f"{shd.meta['shard_devices']} devices")
        for a, b in zip(ref.cells, shd.cells):
            if set(a.metrics) != set(b.metrics) or not all(
                    same(a.metrics[k], b.metrics[k]) for k in a.metrics):
                raise AssertionError(
                    f"{spec.name}: shard_map differs from vmap: "
                    f"{a.metrics} != {b.metrics}")
        out[f"{spec.evaluator}_cells_bitwise_equal"] = len(ref.cells)
    return out


# ------------------------------------------------------------- harness
class _CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def run_phase(name: str, fn, clock: _CompileClock, failures: list) -> None:
    t0, c0 = time.perf_counter(), clock.seconds
    try:
        result = fn()
    except Exception:
        traceback.print_exc()
        failures.append(name)
        print(f"[{name}] FAILED", flush=True)
    else:
        for k, v in result.items():
            print(f"[{name}] {k}: {v}", flush=True)
    print(f"[{name}] wall_s: {time.perf_counter() - t0} "
          f"compile_s: {clock.seconds - c0}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded sweep, over four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and traffic")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no package at {SRC / 'repro'}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"cache_dir: {enable_compile_cache()}")
    print(f"device: {dev.device_kind} x{len(devices)}; "
          f"host: {jax.devices('cpu')}", flush=True)

    clock = _CompileClock()
    failures: list = []
    if args.chips == 4:
        run_phase("sharded", phase_sharded, clock, failures)
    else:
        from repro.configs import get_config

        cfg = get_config(ARCH)
        run_phase("served", lambda: phase_served(
            cfg, servers=4, requests=16, batch_cap=16, chunk=256,
            max_len=2048, seed=args.seed), clock, failures)
        stats = dev.memory_stats() or {}
        print(f"[served] peak_bytes_in_use: "
              f"{stats.get('peak_bytes_in_use', 'not reported')}")
        run_phase("replay", lambda: phase_replay(
            cfg, batch_cap=16, chunk=256, max_len=2048, seed=args.seed),
            clock, failures)
        run_phase("evaluation", phase_evaluation, clock, failures)
        run_phase("kernels", lambda: {**phase_kernels(reps=5),
                                      **kernels_are_compiled(cfg)},
                  clock, failures)
    if failures:
        print(f"chip_smoke: failed phases: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
