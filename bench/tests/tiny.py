"""A checkout-shaped directory that holds one tiny cell, for CPU tests."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
CELL = "tiny.mix"


def make_root(tmp: Path, **limit) -> Path:
    """``tmp`` as a checkout: BENCHMARK.json with the tiny cell, the real
    metric readers, and the tiny configuration and traffic files."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics")
    cfg = json.loads((DATA / "qwen2-tiny.json").read_text())
    cfg["correct"].update(limit)
    (tmp / "bench" / "configs" / "qwen2-tiny.json").write_text(
        json.dumps(cfg))
    shutil.copy(DATA / "tiny_mix.json", tmp / "bench" / "traffic")
    spec["configs"] = [{"name": "qwen2-tiny", "source": "test",
                        "file": "bench/configs/qwen2-tiny.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": CELL, "config": "qwen2-tiny",
                          "traffic": "tiny_mix", "chips": 1, "why": "test"}]
    for m in spec["per_layer"]:
        m["workloads"] = [CELL]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
