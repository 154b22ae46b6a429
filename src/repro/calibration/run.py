"""End-to-end calibration driver + CLI.

``calibrate()`` runs the full pipeline -- grid -> timing backend -> robust
fit -> artifact -- for one architecture from the :mod:`repro.configs`
registry.  The CLI writes the artifact JSON and prints a one-line summary
with the fitted paper constants and fit diagnostics:

    python -m repro.calibration --arch qwen2-0.5b --backend roofline \
        --tiny --out artifacts/calibration/qwen2-0.5b.json
"""

from __future__ import annotations

import argparse
from typing import Optional

from repro.configs import ARCHS, get_config

from .artifact import CalibrationArtifact
from .fit import fit_surfaces
from .grid import CalibrationGrid
from .measure import backend_peaks, collect_samples

__all__ = ["calibrate"]


def calibrate(arch: str = "qwen2-0.5b", *,
              grid: Optional[CalibrationGrid] = None,
              backend: str = "auto", reps: int = 5, reduced: bool = False,
              created: str = "") -> CalibrationArtifact:
    """Time + fit one architecture; returns the artifact (not saved)."""
    grid = grid or CalibrationGrid.default()
    cfg = get_config(arch, reduced=reduced)
    samples = collect_samples(grid, cfg, backend=backend, reps=reps)
    fits = fit_surfaces(samples)
    return CalibrationArtifact(
        arch=arch,
        backend=samples[0].backend,
        grid=grid,
        samples=tuple(samples),
        mix=fits["mix"],
        solo=fits["solo"],
        hw={k: float(v)
            for k, v in backend_peaks(samples[0].backend).items()},
        created=created,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-0.5b", choices=sorted(ARCHS))
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "kernels", "roofline"])
    ap.add_argument("--tiny", action="store_true",
                    help="CI-smoke grid instead of the default grid")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (CPU-smoke) model config")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None, help="artifact JSON path")
    args = ap.parse_args(argv)

    grid = CalibrationGrid.tiny() if args.tiny else CalibrationGrid.default()
    art = calibrate(args.arch, grid=grid, backend=args.backend,
                    reps=args.reps, reduced=args.reduced)
    print(f"[calibrate] {art.arch} backend={art.backend} "
          f"alpha={art.alpha:.6g} beta={art.beta:.6g} "
          f"a_s={art.a_s:.6g} b_s={art.b_s:.6g} "
          f"r2(mix)={art.mix.r2:.4f} r2(solo)={art.solo.r2:.4f}")
    if args.out:
        path = art.save(args.out)
        print(f"[calibrate] wrote {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
