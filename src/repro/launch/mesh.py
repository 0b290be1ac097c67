"""Production mesh builders.

Single pod: 256 chips as (16 data, 16 model). Multi-pod: 2 pods x 256 =
512 chips as (2 pod, 16 data, 16 model), with the "pod" axis crossing the
DCN boundary (collectives on it are costed at DCN, not ICI, bandwidth in
the roofline).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; tests see the
real 1-CPU backend).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding
    constraints and shard_map specs in this repo are written for
    compiler-propagated shardings, not ``Explicit`` sharding-in-types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices the backend actually has."""
    return _mesh((data, model), ("data", "model"))


def make_serving_mesh(data: int | None = None):
    """Data-parallel serving mesh: ``data`` devices (default: all visible)
    on the "data" axis, model axis 1. The serving engine replicates params
    and shards microbatches on "data" via shard_map — the DiT models in
    this repo fit on one chip, so serving scales out, not up."""
    data = data or jax.device_count()
    return _mesh((data, 1), ("data", "model"))


# TPU v5e hardware constants (per chip) used by the roofline analysis.
HW = {
    "peak_bf16_flops": 197e12,      # FLOP/s
    "peak_int8_ops": 394e12,        # int8 OP/s (2x bf16 on the MXU)
    "hbm_bw": 819e9,                # B/s
    "ici_bw": 50e9,                 # B/s per link (per-direction, approx)
    "dcn_bw": 6.25e9,               # B/s per host across pods (approx 50Gbps)
}
