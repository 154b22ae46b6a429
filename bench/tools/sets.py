#!/usr/bin/env python3
"""Run a cell several times, one process per run, and report the spreads.

    python3 bench/tools/sets.py --workload qwen05b.azure_mixed \
        --seeds 1,2,3,4,5,6 --sets 2 [--seconds 30] [--trace 0] \
        [--out chiprun_out/sets.jsonl]

Each run is ``bench/run.py`` as the check runs it, in a child process; this
parent never touches JAX, so the child holds the chip.  Every set uses the
same seeds.  For each metric it prints each set's median and its spread:
the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median, and the
spread without the run farthest from the median.
"""

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values) -> float:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return spread([v for i, v in enumerate(values) if i != far])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=int)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or spec["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            t0 = time.perf_counter()
            p = subprocess.run(
                [sys.executable, "bench/run.py", "--workload", args.workload,
                 "--seed", str(seed), "--seconds", str(seconds), "--trace",
                 str(args.trace)], cwd=ROOT, capture_output=True, text=True)
            wall = time.perf_counter() - t0
            lines = p.stdout.strip().splitlines()
            try:
                res = json.loads(lines[-1])
            except (IndexError, json.JSONDecodeError):
                res = None
            row = {"set": k, "seed": seed, "rc": p.returncode,
                   "command_s": wall, "result": res,
                   "log": [ln for ln in lines[:-1]
                           if ln.startswith(("samples", "compiles", "check",
                                             "decode_roofline"))]}
            if res is None:
                row["stderr_tail"] = p.stderr[-3000:]
            print(json.dumps(row), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            runs.append(row)
        sets.append(runs)
    names = sorted({m for runs in sets for r in runs if r["result"]
                    for m in r["result"]["metrics"]})
    for name in names:
        for k, runs in enumerate(sets):
            vals = [r["result"]["metrics"][name]["value"] for r in runs
                    if r["result"] and name in r["result"]["metrics"]]
            if len(vals) >= 2:
                print(f"{name} set {k}: median={statistics.median(vals)!r} "
                      f"spread={spread(vals)!r} "
                      f"trimmed={trimmed_spread(vals)!r} n={len(vals)}")
    bad = [(r["set"], r["seed"]) for runs in sets for r in runs
           if not (r["result"] and r["result"]["correct"])]
    print(f"not correct or no result: {bad}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
