"""CPU rehearsal of ``chip_smoke.py``: its phases at reduced size with
the Pallas kernels interpreted, its refusal to report without a TPU, and
the compile cache its entry points turn on."""

import functools
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]
REDUCED = get_config("qwen2-0.5b", reduced=True)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_served_phase_reduced(smoke):
    out = smoke.phase_served(REDUCED, servers=2, requests=6, batch_cap=4,
                             chunk=32, max_len=256)
    assert out["requests_completed"] == 6
    assert out["tokens_out"] >= 6


def test_replay_phase_reduced(smoke):
    out = smoke.phase_replay(REDUCED, batch_cap=4, chunk=32, max_len=256)
    assert out["tokens_matched"] >= 4


@pytest.mark.sim
def test_evaluation_phase(smoke):
    out = smoke.phase_evaluation()
    assert out["lp_jax_device"] == str(jax.devices("cpu")[0])
    assert out["ctmc_jax_cells"] == 4 and out["engine_jax_cells"] == 4
    assert out["lp_jax_cells"] == 3


def test_kernels_phase_interpreted(smoke, monkeypatch):
    from repro.kernels.decode_attention import ops as dec_ops
    from repro.kernels.prefill_attention import ops as pf_ops
    from repro.launch import mesh

    # this CPU run interprets the kernels and charges the v5e peaks
    for mod, name in ((dec_ops, "decode_attention"),
                      (pf_ops, "prefill_attention")):
        monkeypatch.setattr(mod, name, functools.partial(
            getattr(mod, name), interpret=True))
    monkeypatch.setitem(mesh.DEVICE_PEAKS, jax.devices()[0].device_kind,
                        mesh.DEVICE_PEAKS["TPU v5 lite"])
    out = smoke.phase_kernels(reps=1, reduced=True)
    assert out["cells"] == 6


def test_calibration_kernels_backend_rejects_unknown_device():
    from repro.calibration import CalibrationGrid
    from repro.calibration.measure import collect_samples

    with pytest.raises(ValueError, match="no peak rates"):
        collect_samples(CalibrationGrid.tiny(), REDUCED, backend="kernels")


def test_sharded_phase_matches_vmap(smoke):
    out = smoke.phase_sharded()
    assert out["devices"] == jax.device_count()
    assert out["ctmc_jax_cells_bitwise_equal"] == 10


def _env(**kw):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **kw)
    return env


@pytest.mark.parametrize("alone", [False, True],
                         ids=["checkout", "script_alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    r = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                       capture_output=True, text=True, timeout=120,
                       env=_env())
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


CACHE_PROBE = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print("DIR=" + enable_compile_cache())
print("CFG=" + str(jax.config.jax_compilation_cache_dir))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def test_compile_cache_uses_the_environment_directory(tmp_path):
    cache = tmp_path / "cache"
    r = subprocess.run([sys.executable, "-c", CACHE_PROBE], cwd=ROOT,
                       capture_output=True, text=True, timeout=120,
                       env=_env(PYTHONPATH="src",
                                JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert r.returncode == 0, r.stderr
    assert f"DIR={cache}" in r.stdout and f"CFG={cache}" in r.stdout
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_the_repo_directory():
    from repro.launch.compile_cache import REPO_CACHE_DIR

    assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    probe = CACHE_PROBE.split("jax.config.update")[0]  # set it, compile nothing
    r = subprocess.run([sys.executable, "-c", probe], cwd=ROOT,
                       capture_output=True, text=True, timeout=120,
                       env=_env(PYTHONPATH="src"))
    assert r.returncode == 0, r.stderr
    assert f"CFG={REPO_CACHE_DIR}" in r.stdout
