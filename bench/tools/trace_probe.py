#!/usr/bin/env python3
"""Record a small profiler trace of the served path and describe it.

    python3 bench/tools/trace_probe.py --out chiprun_out/probe [--seconds 0.3]

Runs the tiny test configuration (``bench/tests/data``) through the served
driver on the chip, traces a short window, copies the ``.xplane.pb`` to
``--out`` and prints each plane's lines with their event counts, a few
events of each device line, and ``bench/trace_reduce.py``'s reduction.
The copied trace is what ``bench/testdata`` keeps for the reducer's test.
"""

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--slice", type=float, default=0.25,
                    help="virtual seconds per slice; fewer steps per slice "
                         "make a smaller trace")
    args = ap.parse_args()

    import jax
    from jax.profiler import ProfileData

    from bench import harness, trace_reduce
    from bench.drivers.served import ServedCell

    harness.find_devices(1)
    harness.enable_compile_cache(ROOT)
    data = ROOT / "bench" / "tests" / "data"
    c = json.loads((data / "qwen2-tiny.json").read_text())
    mix = json.loads((data / "tiny_mix.json").read_text())
    c["deployment"]["slice_virtual_s"] = args.slice
    cell = ServedCell(c, mix, 7, harness.Spans(annotate=True))
    cell.setup()
    tmp = tempfile.mkdtemp()
    harness.start_trace(tmp)
    win = cell.window(args.seconds)
    jax.profiler.stop_trace()
    path = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out / "served_tiny.xplane.pb")
    print(f"trace {path.stat().st_size} bytes; steps={len(win['steps'])}")
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: "
              + ", ".join(f"{ln.name!r}={sum(1 for _ in ln.events)}"
                          for ln in lines))
        if not plane.name.startswith("/device:"):
            continue
        for ln in lines:
            for e in list(ln.events)[:4]:
                stats = {k: str(v)[:60] for k, v in e.stats}
                print(f"   {ln.name} | {e.name[:80]} start={e.start_ns} "
                      f"dur={e.duration_ns} stats={stats}")
    host = [(e.name, e.start_ns, e.duration_ns)
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events
            if e.name.startswith("bench.")]
    print("host spans:", len(host), host[:6])
    print(json.dumps(trace_reduce.reduce_profile(pd), indent=1)[:4000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
