"""Production meshes.

Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods = 512 chips
as (pod=2, data=16, model=16) — the leading axis is the pod-level
data-parallel (and Byzantine-worker) axis.

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax init;
tests and benches see the single real CPU device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(devices, model: int = 1) -> jax.sharding.Mesh:
    """(data, model) mesh over ``devices``: a job's chips, or the CPU
    platform's host devices."""
    data = max(len(devices) // model, 1)
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=devices[:data * model])


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
