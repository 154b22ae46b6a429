"""Fixed-iteration batched LP solver (primal-dual interior point, jit/vmap).

The planning layer (Eqs. 40/42 + SLI rows) needs thousands of small dense
LP solves per sweep/replan epoch; the hand-rolled tableau simplex in
:mod:`repro.core.lp` is exact but serial Python.  This module solves the
same problem form

    maximize    c' x
    subject to  A_ub x <= b_ub
                A_eq x == b_eq
                x >= 0

with a **Mehrotra predictor-corrector interior-point method** whose every
step is a fixed-shape dense linear solve, so one instance jits and a
whole batch of instances runs as a single ``jax.vmap`` over the leading
axis -- the exact same porting pattern as ``ctmc_jax``/``engine_jax``,
with :func:`repro.core.lp.linprog_max` kept as the semantics oracle.

Why interior point (and not a ported simplex): the simplex's pivot
sequence is data-dependent control flow (ragged across a batch), while
the IPM is a *fixed iteration count* of identical Newton steps on the
standard-form KKT system -- ``jax.lax.fori_loop`` of Cholesky solves --
which is the structure ``jit``/``vmap`` want.  Convergence is
superlinear near the central path; on the planning corpus the solver
reaches ~1e-10 relative residuals in < 30 iterations, so the default
budget of ``DEFAULT_ITERS = 60`` has a 2x margin.  Iterates freeze once
converged (steps are masked), so extra budget costs FLOPs, not accuracy.

Numerics: the KKT solves need double precision (normal equations square
the condition number), so the entry points run inside a scoped
``jax.enable_x64`` -- double precision is *local* to the solver and the
process-global default dtype is untouched.  The solve is placed on the
host CPU device on every platform (:func:`solve_device`): the TPU
compiler has no float64 LU decomposition, and one placement gives one
code path and identical answers everywhere.  The
standard-form data is Ruiz-equilibrated before iterating, which is what
keeps the badly scaled planning rows (``theta ~ 3e-4`` next to
``mu_p ~ 1e2``) well conditioned.

Infeasible/unbounded instances cannot raise from inside ``jit``; they
surface as ``converged == False`` with large final residuals in the
:class:`LPBatchResult` diagnostics.  Callers that need hard errors (the
planner) validate inputs first and/or check ``converged``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["LPBatchResult", "solve_lp_batch", "linprog_max_jax",
           "solve_device", "DEFAULT_ITERS", "DEFAULT_TOL"]

DEFAULT_ITERS = 60  # fixed Newton-step budget (see module docstring)
DEFAULT_TOL = 1e-9  # relative primal/dual/complementarity target
_ETA = 0.99  # fraction-to-boundary step damping
_FLOOR = 1e-300  # positivity floor for (z, s) after a step
_RUIZ_ITERS = 6


@dataclass
class LPBatchResult:
    """Batched solver output; every leaf has leading batch axis S.

    ``primal_res`` / ``dual_res`` / ``gap`` are the final *relative*
    residuals (infinity norms over ``1 + |data|``; ``gap`` is the mean
    complementarity over ``1 + |objective|``); ``converged`` is their
    joint ``< tol`` test and ``n_iter`` counts Newton steps actually
    taken before the iterate froze.
    """

    x: np.ndarray  # (S, n) primal solution (original variables)
    fun: np.ndarray  # (S,) objective value c'x of the maximisation
    slack: np.ndarray  # (S, m_ub) slacks of the <= rows
    dual_ub: np.ndarray  # (S, m_ub) duals of <= rows (>= 0)
    dual_eq: np.ndarray  # (S, m_eq) duals of == rows (free sign)
    primal_res: np.ndarray  # (S,)
    dual_res: np.ndarray  # (S,)
    gap: np.ndarray  # (S,)
    converged: np.ndarray  # (S,) bool
    n_iter: np.ndarray  # (S,) int


def _max_step(v, dv):
    """Largest alpha in [0, 1] keeping v + alpha * dv >= 0."""
    ratios = jnp.where(dv < 0, -v / jnp.where(dv < 0, dv, -1.0), jnp.inf)
    return jnp.minimum(1.0, jnp.min(ratios))


def _ruiz(Ah, bh, ch):
    """Ruiz equilibration of the standard-form data + scalar b/c scaling."""
    m, nh = Ah.shape
    Dr = jnp.ones(m, Ah.dtype)
    Dc = jnp.ones(nh, Ah.dtype)

    def body(_, val):
        Ah, Dr, Dc = val
        rn = jnp.max(jnp.abs(Ah), axis=1)
        rs = jnp.where(rn > 0, 1.0 / jnp.sqrt(rn), 1.0)
        Ah = Ah * rs[:, None]
        cn = jnp.max(jnp.abs(Ah), axis=0)
        cs = jnp.where(cn > 0, 1.0 / jnp.sqrt(cn), 1.0)
        Ah = Ah * cs[None, :]
        return Ah, Dr * rs, Dc * cs

    Ah, Dr, Dc = lax.fori_loop(0, _RUIZ_ITERS, body, (Ah, Dr, Dc))
    bs = bh * Dr
    cs = ch * Dc
    beta = jnp.maximum(1.0, jnp.max(jnp.abs(bs)))
    gamma = jnp.maximum(1.0, jnp.max(jnp.abs(cs)))
    return Ah, bs / beta, cs / gamma, Dr, Dc, beta, gamma


def _ipm_core(c, A_ub, b_ub, A_eq, b_eq, tol, iters):
    """One LP instance: max c'x, A_ub x <= b_ub, A_eq x == b_eq, x >= 0.

    Returns a dict of device arrays (see :class:`LPBatchResult`).
    """
    f64 = jnp.float64
    c = c.astype(f64)
    n = c.shape[0]
    m_ub = A_ub.shape[0]
    m_eq = A_eq.shape[0]
    m = m_ub + m_eq
    nh = n + m_ub

    # Standard equality form over z = [x; w]:  Ah z = bh, z >= 0, and the
    # *minimisation* objective ch = -[c; 0] (duals are negated back below).
    Ah = jnp.zeros((m, nh), f64)
    Ah = Ah.at[:m_ub, :n].set(A_ub.astype(f64))
    Ah = Ah.at[:m_ub, n:].set(jnp.eye(m_ub, dtype=f64))
    Ah = Ah.at[m_ub:, :n].set(A_eq.astype(f64))
    bh = jnp.concatenate([b_ub.astype(f64), b_eq.astype(f64)])
    ch = jnp.concatenate([-c, jnp.zeros(m_ub, f64)])

    As, bs, cs, Dr, Dc, beta, gamma = _ruiz(Ah, bh, ch)
    delta = 1e-12  # static primal-dual regularisation of the normal matrix

    # Mehrotra starting point: least-squares (z, y, s) shifted positive.
    # The naive all-ones start stalls on instances whose optimum sits far
    # from the unit box (e.g. very tight / very loose SLI cap rows).
    AAt = As @ As.T
    AAt = AAt + (delta * (1.0 + jnp.trace(AAt) / m)) * jnp.eye(m, dtype=f64)
    L0 = jax.scipy.linalg.cho_factor(AAt, lower=True)
    z_ls = As.T @ jax.scipy.linalg.cho_solve(L0, bs)
    y0 = jax.scipy.linalg.cho_solve(L0, As @ cs)
    s_ls = cs - As.T @ y0
    z_sh = z_ls + jnp.maximum(-1.5 * jnp.min(z_ls), 0.0) + 1e-2
    s_sh = s_ls + jnp.maximum(-1.5 * jnp.min(s_ls), 0.0) + 1e-2
    dot = jnp.dot(z_sh, s_sh)
    z0 = z_sh + 0.5 * dot / jnp.sum(s_sh)
    s0 = s_sh + 0.5 * dot / jnp.sum(z_sh)

    def residuals(z, y, s):
        """Relative residuals on the ORIGINAL (unscaled, max-form) data."""
        z_f = Dc * beta * z
        s_f = (gamma / Dc) * s
        y_f = Dr * gamma * y
        pr = (jnp.max(jnp.abs(bh - Ah @ z_f))
              / (1.0 + jnp.max(jnp.abs(bh))))
        dr = (jnp.max(jnp.abs(ch - Ah.T @ y_f - s_f))
              / (1.0 + jnp.max(jnp.abs(ch))))
        gp = (jnp.dot(z_f, s_f) / nh) / (1.0 + jnp.abs(jnp.dot(ch, z_f)))
        return pr, dr, gp

    reg = 1e-10  # primal-dual regularisation of the augmented system

    def body(_, state):
        z, y, s, done, it = state
        r_p = bs - As @ z
        r_d = cs - As.T @ y - s
        mu = jnp.dot(z, s) / nh
        pr, dr, gp = residuals(z, y, s)
        done = done | ((pr < tol) & (dr < tol) & (gp < tol))

        # Regularised augmented KKT system (quasi-definite; LU-solved).
        # Normal equations A D A' square the conditioning and break down
        # on degenerate optimal faces (d = z/s spans ~1e16 there); the
        # augmented form stays solvable to float64 accuracy.
        K = jnp.zeros((nh + m, nh + m), f64)
        K = K.at[:nh, :nh].set(jnp.diag(-s / z - reg))
        K = K.at[:nh, nh:].set(As.T)
        K = K.at[nh:, :nh].set(As)
        K = K.at[nh:, nh:].set(reg * jnp.eye(m, dtype=f64))
        LU = jax.scipy.linalg.lu_factor(K)

        def direction(tau):
            rhs = jnp.concatenate([r_d - (tau - z * s) / z, r_p])
            sol = jax.scipy.linalg.lu_solve(LU, rhs)
            dz = sol[:nh]
            dy = sol[nh:]
            ds = (tau - z * s - s * dz) / z
            return dz, dy, ds

        # Mehrotra: affine predictor -> centring parameter -> corrector.
        dz_a, dy_a, ds_a = direction(jnp.zeros_like(z))
        a_p = _max_step(z, dz_a)
        a_d = _max_step(s, ds_a)
        mu_aff = jnp.dot(z + a_p * dz_a, s + a_d * ds_a) / nh
        sigma = jnp.clip((mu_aff / jnp.maximum(mu, _FLOOR)) ** 3, 0.0, 1.0)
        dz, dy, ds = direction(sigma * mu - dz_a * ds_a)
        a_p = jnp.minimum(1.0, _ETA * _max_step(z, dz))
        a_d = jnp.minimum(1.0, _ETA * _max_step(s, ds))

        # Frozen-once-converged: jnp.where (not arithmetic masking) so a
        # post-convergence NaN direction can never leak into the iterate.
        z = jnp.where(done, z, jnp.maximum(z + a_p * dz, _FLOOR))
        s = jnp.where(done, s, jnp.maximum(s + a_d * ds, _FLOOR))
        y = jnp.where(done, y, y + a_d * dy)
        it = it + jnp.where(done, 0, 1)
        return z, y, s, done, it

    state0 = (z0, y0, s0, jnp.bool_(False), jnp.int32(0))
    z, y, s, _, it = lax.fori_loop(0, iters, body, state0)

    # Undo the scaling; final diagnostics on the ORIGINAL (max-form) data.
    z_full = Dc * beta * z
    y_min = Dr * gamma * y
    x = z_full[:n]
    slack = z_full[n:]
    y_max = -y_min
    fun = jnp.dot(c, x)
    pr, dr, gp = residuals(z, y, s)
    return {
        "x": x,
        "fun": fun,
        "slack": slack,
        "dual_ub": jnp.maximum(y_max[:m_ub], 0.0),
        "dual_eq": y_max[m_ub:],
        "primal_res": pr,
        "dual_res": dr,
        "gap": gp,
        "converged": (pr < tol) & (dr < tol) & (gp < tol),
        "n_iter": it,
    }


@partial(jax.jit, static_argnames=("iters",))
def _ipm_batch(c, A_ub, b_ub, A_eq, b_eq, tol, iters):
    return jax.vmap(
        lambda cc, G, h, A, b: _ipm_core(cc, G, h, A, b, tol, iters)
    )(c, A_ub, b_ub, A_eq, b_eq)


def solve_device():
    """The device every solve runs on: the host CPU.

    The solver's contract is float64 (rel 1e-6 against the simplex
    oracle, ``docs/PLANNING.md``), and the TPU compiler implements LU
    decomposition in F32/C64 only.  Placing the solve on the CPU on
    every platform, rather than retrying there after a failure, keeps one
    code path with identical answers on every backend.
    """
    return jax.devices("cpu")[0]


def _as_batch(a, shape, name):
    out = np.asarray(a, dtype=np.float64)
    if out.shape != shape:
        raise ValueError(f"{name}: expected shape {shape}, got {out.shape}")
    return out


def solve_lp_batch(
    c: np.ndarray,
    A_ub: np.ndarray = None,
    b_ub: np.ndarray = None,
    A_eq: np.ndarray = None,
    b_eq: np.ndarray = None,
    *,
    iters: int = DEFAULT_ITERS,
    tol: float = DEFAULT_TOL,
) -> LPBatchResult:
    """Solve a batch of ``max c'x s.t. A_ub x <= b_ub, A_eq x == b_eq,
    x >= 0`` instances in one jitted, vmapped interior-point run.

    ``c`` is (S, n); constraint blocks are (S, m, n) / (S, m) with the
    same (m, n) across the batch (pad degenerate instances; values may
    vary freely).  ``None`` blocks mean zero rows.  Returns a
    :class:`LPBatchResult` of host numpy arrays.
    """
    c = np.atleast_2d(np.asarray(c, dtype=np.float64))
    S, n = c.shape
    if A_ub is None:
        A_ub = np.zeros((S, 0, n))
        b_ub = np.zeros((S, 0))
    if A_eq is None:
        A_eq = np.zeros((S, 0, n))
        b_eq = np.zeros((S, 0))
    A_ub = np.asarray(A_ub, dtype=np.float64)
    m_ub = A_ub.shape[1]
    m_eq = np.asarray(A_eq).shape[1]
    A_ub = _as_batch(A_ub, (S, m_ub, n), "A_ub")
    b_ub = _as_batch(b_ub, (S, m_ub), "b_ub")
    A_eq = _as_batch(A_eq, (S, m_eq, n), "A_eq")
    b_eq = _as_batch(b_eq, (S, m_eq), "b_eq")
    with jax.enable_x64(True):
        args = jax.device_put((c, A_ub, b_ub, A_eq, b_eq), solve_device())
        out = _ipm_batch(*args, float(tol), int(iters))
        out = {k: np.asarray(v) for k, v in out.items()}
    return LPBatchResult(**out)


def linprog_max_jax(c, A_ub=None, b_ub=None, A_eq=None, b_eq=None, *,
                    iters: int = DEFAULT_ITERS,
                    tol: float = DEFAULT_TOL) -> LPBatchResult:
    """Single-instance convenience wrapper (batch axis of 1, squeezed).

    Same problem form and result fields as
    :func:`repro.core.lp.linprog_max`; use the oracle when you need
    exact vertex solutions or a basis, use this when you need the jitted
    fixed-iteration path (see ``docs/PLANNING.md`` for the decision
    table).
    """
    c = np.asarray(c, dtype=np.float64)

    def up(a, rows=False):
        if a is None:
            return None
        a = np.asarray(a, dtype=np.float64)
        return a[None] if rows else np.atleast_2d(a)[None]

    res = solve_lp_batch(c[None], up(A_ub), up(b_ub, rows=True),
                         up(A_eq), up(b_eq, rows=True),
                         iters=iters, tol=tol)
    return LPBatchResult(**{k: v[0] for k, v in res.__dict__.items()})
