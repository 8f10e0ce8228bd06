"""Production mesh construction (DESIGN.md §5).

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis crosses the DCI; only DP gradient all-reduce (optionally int8-
compressed, repro.optim.compression) travels on it.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and only
launch/dryrun.py forces 512 host devices).
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types: sharding stays implicit
    (``with_sharding_constraint`` / ``NamedSharding``), where the
    installed jax would default new meshes to Explicit axes."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over the first ``data * model`` local devices (chips,
    or forced host devices on a CPU) — used by smoke/distributed tests
    (8 forced devices), the scale-out executor path (``data_parallel=``)
    and single-device runs. Each mesh position is a distinct device.

    Validates the request against the live device count up front: the
    raw ``make_mesh`` reshape error ("cannot reshape array of size 1
    into shape (2, 1)") says nothing about WHY there aren't enough
    devices or how to get more on a CPU host.
    """
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got data={data}, "
                         f"model={model}")
    have = jax.device_count()
    if data * model > have:
        platform = jax.default_backend()
        if platform == "cpu":
            hint = (f"launch with XLA_FLAGS=--xla_force_host_platform_"
                    f"device_count={data * model} (set before jax "
                    f"initialises) or shrink the mesh")
        else:
            hint = ("shrink the mesh or run on a host with more "
                    "chips")
        raise ValueError(
            f"make_host_mesh(data={data}, model={model}) needs "
            f"{data * model} devices but this host has {have} "
            f"{platform} device{'' if have == 1 else 's'} — {hint}")
    return make_mesh((data, model), ("data", "model"))


def mesh_chips(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
