"""SPMD sharding layer for the batched sweep engines.

Every JAX evaluator in this repo reduces a grid of independent cells --
(mix, policy, n, seed) replications for the simulators, stacked LP
instances for the planner -- to "one kernel, many leading-axis items".
This module partitions that leading axis over the 1-D ``"cells"`` mesh
(:func:`repro.launch.mesh.cells_mesh`) behind one dispatch path:

* ``placement="single"``    one jitted kernel call per cell (debug /
  memory floor);
* ``placement="vmap"``      the classic single-device batch -- the
  **bitwise oracle** every other placement must reproduce exactly;
* ``placement="shard_map"`` the batch partitioned across devices via
  ``shard_map``; per-cell independence (no collectives inside the
  kernel) keeps it bitwise identical to the vmap oracle at any device
  count.

Three properties make the layer safe on arbitrary grids:

* **Host-count-agnostic PRNG** -- every cell's key derives from its
  *grid coordinates* (``cell_seed_sequence`` -> ``cell_int_seed`` ->
  ``jax.random.PRNGKey``), never from its device placement, so 1 device and N
  devices draw identical randomness.
* **Padded-cell masking** -- a ragged batch (``n_cells`` not a multiple
  of the mesh) is padded by repeating cell 0; the padded lanes compute
  real (discarded) work and the host slice ``[:n_cells]`` masks them
  out before anyone reads the results.
* **Device-memory-aware tiling** -- :func:`plan_shards` caps the cells
  resident per device (explicitly or from a ``bytes_per_cell`` /
  ``memory_budget`` estimate) and the runner loops the batch through
  ``n_tiles`` equal-shape passes, so grids larger than device memory
  shard in chunks under ONE compiled executable.

See ``docs/SHARDING.md`` for the mesh layout and the tiling math.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Optional

import numpy as np

__all__ = [
    "PLACEMENTS",
    "ShardPlan",
    "plan_shards",
    "pad_batch",
    "run_sharded",
    "detected_devices",
    "warn_once",
    "reset_warn_once",
]

# every way a batch engine can execute its cell batch; "vmap" is the
# single-device oracle, "shard_map" must match it bitwise
PLACEMENTS = ("single", "vmap", "shard_map")

def detected_devices() -> int:
    import jax

    return jax.device_count()


# Process-wide once-per-kind warning guard; tests re-arm it with
# ``reset_warn_once``.
_warned_once: set = set()


def warn_once(kind: str, message: str, *, stacklevel: int = 3) -> bool:
    """Emit ``message`` as a RuntimeWarning the first time ``kind`` is seen.

    Returns True if the warning fired, False if ``kind`` already warned
    in this process.
    """
    if kind in _warned_once:
        return False
    _warned_once.add(kind)
    warnings.warn(message, RuntimeWarning, stacklevel=stacklevel + 1)
    return True


def reset_warn_once(kind: Optional[str] = None) -> None:
    """Re-arm the once-per-kind guard (all kinds when ``kind`` is None)."""
    if kind is None:
        _warned_once.clear()
    else:
        _warned_once.discard(kind)


def _warn_serialized(n_devices: int) -> None:
    """Once per process: a shard_map placement that landed on one device
    is a correct but serial run."""
    warn_once(
        "shard-serial",
        f"placement='shard_map' is running on a 1-device mesh "
        f"({n_devices} device detected): results are exact but the "
        f"batch is not partitioned across devices",
        stacklevel=4)


@dataclass(frozen=True)
class ShardPlan:
    """How one cell batch lays out over the mesh.

    ``per_device`` cells sit on each of ``n_devices`` devices per pass,
    so one pass covers ``tile = n_devices * per_device`` cells and the
    batch takes ``n_tiles`` equal-shape passes (one compile); the final
    ``padded - n_cells`` lanes are padding, masked off on the host.
    """

    n_cells: int
    n_devices: int
    per_device: int

    def __post_init__(self) -> None:
        if self.n_cells < 1 or self.n_devices < 1 or self.per_device < 1:
            raise ValueError(f"degenerate shard plan: {self}")

    @property
    def tile(self) -> int:
        return self.n_devices * self.per_device

    @property
    def n_tiles(self) -> int:
        return -(-self.n_cells // self.tile)

    @property
    def padded(self) -> int:
        return self.n_tiles * self.tile

    @property
    def n_padding(self) -> int:
        return self.padded - self.n_cells

    def report(self) -> dict:
        return {
            "n_cells": self.n_cells, "n_devices": self.n_devices,
            "per_device": self.per_device, "tile": self.tile,
            "n_tiles": self.n_tiles, "n_padding": self.n_padding,
        }


def plan_shards(n_cells: int, *, n_devices: Optional[int] = None,
                max_cells_per_device: Optional[int] = None,
                bytes_per_cell: Optional[float] = None,
                memory_budget: Optional[float] = None) -> ShardPlan:
    """Tile a batch of ``n_cells`` over the devices.

    Default: one pass, ``per_device = ceil(n_cells / n_devices)``.  A
    cap -- ``max_cells_per_device`` directly, or derived as
    ``floor(memory_budget / bytes_per_cell)`` from a per-cell footprint
    estimate -- splits the batch into multiple equal-shape tiles so the
    per-device working set never exceeds the cap.
    """
    if n_cells < 1:
        raise ValueError(f"n_cells must be >= 1, got {n_cells}")
    d = int(n_devices) if n_devices is not None else detected_devices()
    cap = max_cells_per_device
    if bytes_per_cell is not None and memory_budget is not None:
        if bytes_per_cell <= 0:
            raise ValueError("bytes_per_cell must be positive")
        by_mem = max(1, int(memory_budget // bytes_per_cell))
        cap = by_mem if cap is None else min(int(cap), by_mem)
    per = -(-n_cells // d)
    if cap is not None:
        if cap < 1:
            raise ValueError(f"cell cap must be >= 1, got {cap}")
        per = min(per, int(cap))
    return ShardPlan(n_cells=int(n_cells), n_devices=d, per_device=per)


def pad_batch(batched, padded: int):
    """Pad every leaf of ``batched`` along axis 0 to length ``padded`` by
    repeating item 0 (a real cell: its padding lanes compute valid,
    discarded work, so no kernel ever sees out-of-distribution zeros)."""
    import jax
    import jax.numpy as jnp

    def pad(leaf):
        n = leaf.shape[0]
        if n == padded:
            return leaf
        reps = jnp.broadcast_to(leaf[:1],
                                (padded - n,) + tuple(leaf.shape[1:]))
        return jnp.concatenate([leaf, reps], axis=0)

    return jax.tree_util.tree_map(pad, batched)


def run_sharded(kernel, replicated, batched, *,
                plan: Optional[ShardPlan] = None,
                mesh=None,
                n_devices: Optional[int] = None,
                max_cells_per_device: Optional[int] = None,
                bytes_per_cell: Optional[float] = None,
                memory_budget: Optional[float] = None):
    """Evaluate ``kernel(replicated, item)`` for every leading-axis item
    of the ``batched`` pytree, partitioned over the cells mesh.

    Returns ``(raw, report)``: ``raw`` mirrors the kernel's output
    pytree with a leading axis of exactly ``n_cells`` (padding masked
    off, tiles re-concatenated on the host as numpy arrays), ``report``
    is the :meth:`ShardPlan.report` dict plus the serialized flag.
    """
    import jax

    from repro.launch.mesh import cells_mesh, shard_cells_fn

    leaves = jax.tree_util.tree_leaves(batched)
    if not leaves:
        raise ValueError("run_sharded got an empty batched pytree")
    n_cells = int(leaves[0].shape[0])
    if plan is None:
        plan = plan_shards(n_cells, n_devices=n_devices,
                           max_cells_per_device=max_cells_per_device,
                           bytes_per_cell=bytes_per_cell,
                           memory_budget=memory_budget)
    elif plan.n_cells != n_cells:
        raise ValueError(f"plan is for {plan.n_cells} cells, batch has "
                         f"{n_cells}")
    if mesh is None:
        mesh = cells_mesh(plan.n_devices)
    if plan.n_devices == 1:
        _warn_serialized(plan.n_devices)

    fn = shard_cells_fn(kernel, mesh=mesh)  # ONE compile for all tiles
    full = pad_batch(batched, plan.padded)
    tiles = []
    for t in range(plan.n_tiles):
        sl = slice(t * plan.tile, (t + 1) * plan.tile)
        part = jax.tree_util.tree_map(lambda leaf: leaf[sl], full)
        out = fn(replicated, part)
        tiles.append(jax.tree_util.tree_map(np.asarray, out))
    raw = (tiles[0] if plan.n_tiles == 1 else jax.tree_util.tree_map(
        lambda *xs: np.concatenate(xs, axis=0), *tiles))
    raw = jax.tree_util.tree_map(lambda leaf: leaf[:n_cells], raw)
    report = dict(plan.report(), serialized=bool(plan.n_devices == 1))
    return raw, report
