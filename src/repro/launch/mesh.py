"""Mesh construction and per-chip peaks.

Target: TPU v5e, 256 chips/pod (16x16), optionally 2 pods (512 chips).
Importing this module never touches jax device state — meshes are built
lazily by the functions below (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
see launch/dryrun.py)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Every mesh of the repo is built here, with `Auto` axes.

    `jax.make_mesh` defaults to `Explicit` axis types, under which an
    unannotated gather (the embedding lookup) raises ShardingTypeError;
    the serving and training paths place data with NamedSharding and
    sharding constraints, which is the Auto contract.  `devices` picks
    the devices the mesh spans (default: the first prod(shape) of
    jax.devices()) — e.g. a (1, 1) mesh over one chip per router
    replica."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Tiny mesh for CI-scale sharding tests (requires >= 8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """Axes that shard the batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def has_pod_axis(mesh) -> bool:
    return "pod" in mesh.axis_names


# Published per-chip peaks, keyed by jax's `device_kind`.  Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s and 16 GB of
# HBM, 1,600 Gbit/s of inter-chip interconnect over 4 links).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "hbm_bw": 819e9,             # B/s
        "ici_link_bw": 50e9,         # B/s per link
        "hbm_bytes": 16e9,           # B
    },
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of `device_kind`; a kind not in PEAKS is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
