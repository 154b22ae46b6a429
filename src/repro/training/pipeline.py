"""Pipeline-parallel train step (GPipe-style) over a mesh "pipe" axis.

For the largest assigned models an optional third parallelism axis: layers
are split into ``n_stages`` contiguous stages; microbatches stream through
stages with ``jax.lax.ppermute`` boundary transfers inside a ``shard_map``.
The schedule is the standard GPipe fill/drain loop expressed as a
``lax.scan`` over ``n_micro + n_stages - 1`` ticks, with each stage either
idle, forwarding, or (in the backward scan) accumulating grads -- a
deterministic, compiler-visible schedule with bubble fraction
``(S-1)/(M+S-1)`` (reported by :func:`bubble_fraction`).

This module implements forward-only pipelining for inference-style use and
a loss-through-pipeline trick for training: the scanned stage function is
differentiated as a whole (jax.grad through shard_map+ppermute), which is
correct albeit less memory-lean than hand-rolled 1F1B; remat inside each
stage keeps activations bounded.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["bubble_fraction", "make_pipeline_forward"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def make_pipeline_forward(stage_fn, mesh, *, n_micro: int, axis: str = "pipe"):
    """stage_fn(stage_params, x, stage_id) -> y, applied per stage.

    Returns ``f(stacked_stage_params, x_micro)`` where ``x_micro`` has
    leading dim n_micro; output is the final-stage stream, same leading dim.
    Runs as a shard_map over ``axis``; stage s holds stage s's params.
    """
    S = mesh.shape[axis]
    ticks = n_micro + S - 1

    def per_stage(params_local, xs_local):
        # params_local: this stage's params (leading stage dim stripped by
        # shard_map partitioning); xs_local: full microbatch stream
        # (replicated over the pipe axis; only stage 0 consumes it).
        sid = jax.lax.axis_index(axis)
        x0 = xs_local[0]
        buf = jnp.zeros_like(x0)  # inter-stage register
        outs = jnp.zeros((n_micro,) + x0.shape, x0.dtype)
        # carries become device-varying inside the loop; mark them so
        buf = jax.lax.pcast(buf, (axis,), to="varying")
        outs = jax.lax.pcast(outs, (axis,), to="varying")

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if in range); others use buf
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(sid == 0, xs_local[inject], buf)
            y = stage_fn(params_local, x_in, sid)
            # valid iff this stage is processing a real microbatch at tick t
            mb = t - sid
            valid = (mb >= 0) & (mb < n_micro)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # last stage writes outs[mb]; others forward to the next stage
            write = (sid == S - 1) & valid
            mb_idx = jnp.clip(mb, 0, n_micro - 1)
            outs = outs.at[mb_idx].set(
                jnp.where(write, y, outs[mb_idx]))
            buf_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            return (buf_next, outs), ()

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage's outs are real; zero-fill + psum broadcasts
        # them to every stage (and restores the replicated type for vma)
        outs = jnp.where(sid == S - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
