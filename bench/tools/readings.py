#!/usr/bin/env python3
"""Readings that a cell's limit is set from: the program's and the control's.

    python3 bench/tools/readings.py --workload qwen05b.azure_mixed \
        --seeds 11,12,13 --seconds 15 [--out chiprun_out/readings.jsonl]

For each seed, in this one process: set the cell up, run a window of
``--seconds`` at the cell's own load, free the fleet, and read the same
sample that a run's check reads twice against the float32 reference:
the served tokens (the program's reading, ``max_logit_gap``) and, at the
same positions, the tokens that the float8 pass puts first (the control's
reading, ``control_gap``).  Prints one JSON line per seed.  The benchmark's
own runs never run the control.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out")
    args = ap.parse_args()

    import importlib

    from bench import harness

    spec, w, c, mix = harness.load_cell(ROOT, args.workload)
    harness.find_devices(int(w["chips"]))
    harness.enable_compile_cache(ROOT)
    driver = importlib.import_module(f"bench.drivers.{c['driver']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = driver.make(c, mix, seed, harness.Spans())
        cell.setup()
        cell.window(args.seconds)
        cell.release()
        seqs = [(cell.prompts[r.rid], r.out_tokens) for r in cell.sample()]
        t1 = time.perf_counter()
        got = cell.ref.max_logit_gap(c, cell.params, seqs,
                                     max_len=int(cell.dep["max_len"]),
                                     control=True)
        row = {"seed": seed, "max_logit_gap": got["max_logit_gap"],
               "control_gap": got["control_gap"], "tokens": got["tokens"],
               "requests": len(seqs), "per_sequence": got["per_sequence"],
               "run_s": t1 - t0, "reference_s": time.perf_counter() - t1}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
