"""Production mesh factories + the sweep's SPMD "cells" mesh.

A *logical server* in the paper's queueing model is one TP group = one
"model"-axis slice of the mesh; the "data" axis enumerates logical servers
for serving and is the FSDP/DP axis for training; the "pod" axis extends
either scheme across pods.  For *simulation* the unit of parallelism is a
grid cell (one (mix, policy, n, seed) replication), so the batch engines
shard over a 1-D mesh whose single axis is named ``"cells"``
(:func:`cells_mesh`); :func:`shard_cells` is the raw shard_map primitive
over that axis (strict -- the grid-level padding/tiling lives in
:mod:`repro.sweep.sharded`).  Defined as functions (never module-level
constants) so importing this module never touches jax device state.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["make_mesh", "make_production_mesh", "v5e_constants",
           "device_peaks", "DEVICE_PEAKS", "cells_mesh", "shard_cells",
           "shard_cells_fn"]


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (the sharding
    propagation these meshes were written for; jax defaults to Explicit)."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def cells_mesh(n_devices: Optional[int] = None):
    """1-D mesh over the host's devices; its only axis is ``"cells"``.

    Every sharded sweep partitions its flattened grid-cell batch over
    this axis.  ``n_devices`` defaults to ``jax.device_count()`` (all
    visible devices); pass a smaller count to leave devices free.
    """
    import jax

    d = int(n_devices) if n_devices is not None else jax.device_count()
    if d < 1:
        raise ValueError(f"cells_mesh needs >= 1 device, got {d}")
    return make_mesh((d,), ("cells",))


def shard_cells_fn(kernel, *, mesh):
    """Build the jitted "cells"-sharded batch executable for ``kernel``.

    The returned callable ``fn(replicated, batched)`` vmaps
    ``kernel(replicated, item)`` over the leading axis of every leaf of
    the ``batched`` pytree, partitioned over ``mesh``'s ``"cells"``
    axis; ``replicated`` is broadcast to every device.  Build it ONCE
    and call it per equal-shape tile -- jit caches on the callable, so
    a multi-tile batch compiles a single executable.  Strict by design:
    the leading axis must divide evenly by the mesh size (ragged grids
    are padded/tiled one layer up, in :mod:`repro.sweep.sharded`).
    Per-cell independence (no collectives in ``kernel``) is what makes
    the result bitwise identical to a plain single-device ``jax.vmap``
    -- the property the device-count-invariance tests pin down.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    d = mesh.devices.size

    def vmapped(rep, bat):
        return jax.vmap(lambda b: kernel(rep, b))(bat)

    sharded = jax.shard_map(vmapped, mesh=mesh, in_specs=(P(), P("cells")),
                            out_specs=P("cells"), check_vma=False)
    jitted = jax.jit(sharded)

    def fn(replicated, batched):
        leaves = jax.tree_util.tree_leaves(batched)
        if not leaves:
            raise ValueError("shard_cells got an empty batched pytree")
        n = leaves[0].shape[0]
        if n % d != 0:
            raise ValueError(
                f"shard_cells is strict: {n} cells do not divide over "
                f"{d} devices (pad via repro.sweep.sharded)")
        return jitted(replicated, batched)

    return fn


def shard_cells(kernel, replicated, batched, *, mesh):
    """One-shot convenience wrapper over :func:`shard_cells_fn`."""
    return shard_cells_fn(kernel, mesh=mesh)(replicated, batched)


#: Per-chip peak rates keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect
#: over a 2D torus of 4 links per chip).
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,  # FLOP/s
        "hbm_bw": 819e9,            # B/s
        "ici_link_bw": 50e9,        # B/s per link (~45-50 GB/s each way)
        "hbm_bytes": 16 * 1024**3,  # 16 GiB
        "ici_links": 4,             # 2D torus: 4 links per chip
    },
}


def device_peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; unknown kinds raise."""
    if device_kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r}; add it to "
            f"repro.launch.mesh.DEVICE_PEAKS with its source "
            f"(known: {sorted(DEVICE_PEAKS)})")
    return dict(DEVICE_PEAKS[device_kind])


def v5e_constants() -> dict:
    """TPU v5e per-chip constants: the deterministic roofline model's
    stated hardware, whatever device is attached."""
    return device_peaks("TPU v5 lite")
