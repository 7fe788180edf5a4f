"""Production mesh construction (brief §MULTI-POD DRY-RUN).

A function — not a module-level constant — so importing this module never
touches jax device state; the dry-run sets
``xla_force_host_platform_device_count`` before the first jax call.
"""

from __future__ import annotations

import jax

from repro.config import MeshConfig


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (classic GSPMD propagation;
    jax's default is Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axis types are Auto (classic GSPMD propagation): the framework supplies
    in/out shardings + a few activation constraints and lets the partitioner
    fill in the rest.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MeshConfig(multi_pod=multi_pod)


def make_mesh_from_config(mesh_cfg: MeshConfig):
    return make_mesh(mesh_cfg.shape, mesh_cfg.axes)


def make_engine_mesh():
    """``("data", "model")`` mesh over the local devices for the sharded
    FlatModel engine (``engine="sharded"``, docs/SHARDING.md).

    All devices go to the ``model`` axis — the engine shards the flat
    parameter axis N and replicates cohort rows. Returns None on a single
    device (sharding would be a no-op; ``make_engine`` falls back to the
    batched engine).
    """
    n = jax.device_count()
    if n < 2:
        return None
    return make_mesh((1, n), ("data", "model"))
