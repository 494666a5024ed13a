"""Device meshes.

Every mesh of the repo is built by :func:`make_mesh`, which gives all axes
the ``Auto`` axis type: the sharded paths are written for GSPMD propagation
plus ``shard_map`` regions (DESIGN.md §3, §7, §9), and ``jax.make_mesh``'s
own default (``Explicit`` axes) rejects their constraints and gathers.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model").

DynaBRO workers = the data-parallel groups: m=16 (single pod) or m=32
(pod × data combined) — each worker is one model-parallel slice of 16 chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis of type ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def worker_axes(mesh) -> tuple:
    """The axes across which DynaBRO workers are laid out."""
    return tuple(a for a in mesh.axis_names if a != "model")


def n_workers(mesh) -> int:
    n = 1
    for a in worker_axes(mesh):
        n *= mesh.shape[a]
    return n


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return make_mesh(shape, axes)


def worker_spec(waxes):
    """PartitionSpec entry for a leading worker axis: the tuple of worker mesh
    axes, collapsed to the bare name when there is only one."""
    return tuple(waxes) if len(waxes) > 1 else waxes[0]


def worker_iota(m: int):
    """The worker-index-as-data iota (DESIGN.md §3): sharded over the worker
    axes, each device's local slice is its own flattened worker index."""
    import jax.numpy as jnp

    return jnp.arange(m, dtype=jnp.float32)


def make_worker_mesh(n_devices: int = 0, axis: str = "workers",
                     model: int = 0):
    """Worker mesh for the sharded compiled driver (DESIGN.md §7, §9).

    ``model=0`` (default) builds the 1-D ``(workers,)`` mesh of the
    fully-manual shard_map path; ``n_devices=0`` uses every device and
    ``n_devices=1`` gives the parity-contract mesh (bitwise-identical to the
    unsharded driver).

    ``model>=1`` builds the 2-axis ``(workers, model)`` mesh of the model-zoo
    GSPMD path: ``n_devices`` (0 = whatever the model axis leaves over)
    counts the *worker*-axis size, and the per-leaf FSDP/model partition
    rules of ``launch.sharding.plan_params`` apply unchanged (the worker
    axis doubles as the FSDP axis, exactly like Mode B's 'data'). A
    ``(1, 1)`` mesh is the parity-contract mesh of this path."""
    devs = jax.devices()
    if model:
        n = n_devices or max(1, len(devs) // model)
        if n * model > len(devs):
            raise ValueError(
                f"requested {n}x{model} devices, have {len(devs)}")
        return make_mesh((n, model), (axis, "model"), devices=devs[: n * model])
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return make_mesh((n,), (axis,), devices=devs[:n])


def make_lane_mesh(n_lanes: int = 0, n_workers: int = 1,
                   lane_axis: str = "lanes", worker_axis: str = "workers"):
    """2-axis ``(lanes, workers)`` mesh for the sharded vmapped sweep
    (DESIGN.md §12): the sweep's cell lanes are split over ``lane_axis``
    and, with ``n_workers > 1``, each lane's per-worker gradient vmap over
    ``worker_axis`` (the 1-axis driver's worker sharding, nested inside the
    lane split). ``n_lanes=0`` uses whatever the worker axis leaves over;
    a ``(1, 1)`` mesh is this path's parity-contract mesh — the sweep skips
    the shard_map wrap entirely, so it is bitwise-identical to the
    unsharded sweep by construction."""
    devs = jax.devices()
    if n_workers < 1:
        raise ValueError(f"n_workers must be >= 1, got {n_workers}")
    n = n_lanes or max(1, len(devs) // n_workers)
    if n * n_workers > len(devs):
        raise ValueError(
            f"requested {n}x{n_workers} devices, have {len(devs)}")
    return make_mesh((n, n_workers), (lane_axis, worker_axis),
                     devices=devs[: n * n_workers])
