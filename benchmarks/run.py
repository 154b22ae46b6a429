"""Run the whole benchmark suite: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only name[,name]]

Artifacts land in artifacts/bench/*.json; each bench prints its table.
"""

from __future__ import annotations

import argparse
import time
import traceback

from . import (bench_ablations, bench_calibration, bench_charging,
               bench_classes, bench_convergence, bench_ctmc_speed,
               bench_engine_speed, bench_frontier, bench_heterogeneity,
               bench_matched, bench_optimality_gap, bench_roofline,
               bench_scale_sweep, bench_scenarios, bench_sensitivity,
               bench_sli_pareto, bench_trace_replay)
from .common import ART


class _SweepCLI:
    """Suite adapter delegating to the ``python -m repro.sweep.run`` CLI."""

    @staticmethod
    def run(quick: bool = True):
        from repro.sweep.run import main as sweep_main

        argv = ["--name", "suite",
                "--out", str(ART.parent / "sweep" / "suite.json")]
        if quick:
            argv.append("--quick")
        rc = sweep_main(argv)
        if rc:
            raise RuntimeError(f"sweep CLI exited with {rc}")


SUITE = [
    ("calibration", bench_calibration),        # Fig 3
    ("charging", bench_charging),              # Fig 2 / Section 5.1
    ("trace_replay", bench_trace_replay),      # Table 2 / Fig 4
    ("frontier", bench_frontier),              # Fig 5
    ("sli_pareto", bench_sli_pareto),          # Fig 6
    ("sensitivity", bench_sensitivity),        # Figs 7-8
    ("matched", bench_matched),                # EC.8.2
    ("scale_sweep", bench_scale_sweep),        # EC.8.3
    ("classes", bench_classes),                # EC.8.4
    ("scenarios", bench_scenarios),            # workload registry closed loop
    ("convergence", bench_convergence),        # EC.8.5
    ("optimality_gap", bench_optimality_gap),  # Theorems 2-3 vanishing gap
    ("heterogeneity", bench_heterogeneity),    # mixed-fleet class-aware study
    ("ctmc_speed", bench_ctmc_speed),          # uniformized engine micro-bench
    ("engine_speed", bench_engine_speed),      # trace-replay engine micro-bench
    ("ablations", bench_ablations),            # EC.8.6
    ("sweep", _SweepCLI),                      # repro.sweep.run default grid
    ("roofline", bench_roofline),              # dry-run roofline table
]


def _artifact_state() -> dict:
    """(size, mtime_ns) per JSON artifact under artifacts/ -- cheap
    before/after snapshot to detect a bench that silently wrote
    nothing."""
    root = ART.parent
    return {str(p): (p.stat().st_size, p.stat().st_mtime_ns)
            for p in root.rglob("*.json") if p.is_file()}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow); default is quick mode")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    failures = []
    for name, mod in SUITE:
        if only and name not in only:
            continue
        print(f"\n{'=' * 72}\n== bench: {name}\n{'=' * 72}", flush=True)
        t0 = time.time()
        before = _artifact_state()
        try:
            mod.run(quick=not args.full)
            print(f"== {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
            continue
        if args.full and _artifact_state() == before:
            # a full-mode bench that writes no artifact produced nothing
            # a paper table can cite; fail loudly instead of shipping a
            # green run with a silent hole in artifacts/bench/
            failures.append(name)
            print(f"== {name}: FAILED -- wrote no artifact under "
                  f"{ART.parent} in --full mode (every full-mode bench "
                  f"must save() its table)", flush=True)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks green")


if __name__ == "__main__":
    main()
