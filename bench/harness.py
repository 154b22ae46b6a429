"""The benchmark's harness: one cell of ``BENCHMARK.json``, run and reported.

Everything that belongs to one cell is found by name.  The workload entry
names a configuration, whose ``file`` holds its sizes and names its driver
(``bench/drivers/<driver>.py``) and reference; the traffic mix is
``bench/traffic/<traffic>.json`` and names its generator
(``bench/generators/<generator>.py``); every metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a mix or a metric adds files
and entries and edits none.

A driver module has ``make(config, mix, seed, spans)``, which returns a cell
with ``setup()``; ``window(seconds)``, whose record holds ``window_s``,
``arrived`` and a ``samples`` dict of counts to print, beside whatever its
metric readers read; ``release()``; and ``check()``, which returns the
``numbers`` compared as ``(value, limit)``, a ``samples`` dict and the count
``failed``.  It may name in ``TRACE_SYNC`` the host span and programs that
``bench/trace_reduce.py`` pairs.

A run: check the devices, turn on JAX's persistent compilation cache inside
the checkout, set up the cell (its time is ``setup_s``), measure the window
with the profiler off (``--trace 0``) or on (``--trace 1``), read the
device's peak memory, free the program's state, check what the window
produced against the reference, and print the result as the last line of
standard output.  The numbers compared are printed beside their limits as
the last lines of standard error and under the result's last key.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = Path("bench") / ".cache" / "jax"

__all__ = ["Spans", "CompileCounter", "load_cell", "cell_metrics",
           "read_metric", "run", "main"]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Spans:
    """Names host spans in the profiler's trace while it records."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts XLA compilations and sums their seconds (``jax.monitoring``)."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> tuple:
    """``(spec, workload entry, configuration, traffic mix)`` by name."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    mix = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return spec, w, config, mix


def cell_metrics(spec: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def read_metric(root: Path, name: str, rec: dict):
    """``bench/metrics/<name>.py``'s ``read(rec)``: a number or None."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def start_trace(trace_dir: str) -> None:
    """The profiler on, without its Python-function tracer (which slows the
    host many times over and floods the trace)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_devices(chips: int, *, require_tpu: bool = True) -> list:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"cell asks for {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: Path) -> Path:
    """JAX's persistent cache at a fixed directory inside the checkout,
    holding every program however quick its compile."""
    import jax

    path = (root / CACHE_DIR).resolve()
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _peaks(root: Path, kind: str) -> dict:
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _pairs(d: dict) -> str:
    return " ".join(f"{k}={v}" for k, v in d.items())


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, require_tpu: bool = True, t_start: float = None) -> dict:
    """One run of one cell; returns the result object (not printed)."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec, w, config, mix = load_cell(root, workload)
    devices = find_devices(int(w["chips"]), require_tpu=require_tpu)
    peaks = _peaks(root, devices[0].device_kind) if require_tpu else None
    enable_compile_cache(root)
    import jax

    from bench.trace_reduce import reduce_trace

    compiles = CompileCounter()
    spans = Spans(annotate=trace)
    driver = importlib.import_module(f"bench.drivers.{config['driver']}")
    cell = driver.make(config, mix, seed, spans)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    c0 = compiles.count
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        start_trace(trace_dir)
    try:
        win = cell.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiled_in_window = compiles.count - c0
    reduced = None
    if trace:
        try:
            reduced = reduce_trace(trace_dir, n_devices=len(devices),
                                   sync=getattr(driver, "TRACE_SYNC", None))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    memory_peak = _memory_peak(devices)
    cell.release()
    checked = cell.check()

    dev = devices[0]
    rec = {"setup_s": setup_s, "window": win, "trace": reduced,
           "config": config, "mix": mix, "device_kind": dev.device_kind,
           "peaks": peaks, "notes": []}
    metrics = {}
    for m in cell_metrics(spec, workload, trace):
        v = read_metric(root, m["name"], rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    print("samples:", _pairs(dict(win["samples"], window_s=win["window_s"])))
    print(f"compiles: in_window={compiled_in_window} total={compiles.count} "
          f"compile_s={compiles.seconds}")
    for note in rec["notes"]:
        print(note)
    print("check:", _pairs(checked["samples"]))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    numbers = checked["numbers"]
    correct = all(lim is not None and math.isfinite(v) and v <= lim
                  for v, lim in numbers.values()) and checked["failed"] == 0
    out = {"correct": bool(correct), "attempted": int(win["arrived"]),
           "failed": int(checked["failed"]), "metrics": metrics,
           "device": device}
    if reduced is not None:
        out["breakdown"] = reduced["breakdown"]
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in numbers.items()}
    return out


def main(argv=None, *, t_start: float = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program at {ROOT / 'src' / 'repro'}",
              file=sys.stderr)
        return 2
    try:
        out = run(ROOT, args.workload, args.seed, args.seconds,
                  bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for k, v in out["checks"].items():
        print(f"check {k}: value={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
