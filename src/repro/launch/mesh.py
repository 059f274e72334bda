"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axes: the engine places its buffers with
    shard_map specs and leaves the rest of the program to the partitioner
    (``jax.make_mesh`` defaults to Explicit axes, which type every split)."""
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) single-pod or (2, 16, 16) multi-pod production mesh.

    The flat-buffer engine's row shards ride the existing "model" axis
    (``EngineConfig(shard_axis="model", shards=16)``): engine rows and
    tensor-parallel model dims shard over the SAME 16 devices, so the
    engine state stops replicating across the tensor group — a 16x
    per-device engine-HBM cut with zero extra mesh axes.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()[:n]
    return make_mesh(shape, axes, devices=devices)


def make_engine_mesh(workers: int, *, shards: int = 1, pods: int = 0,
                     shard_axis: str = "shard", devices=None):
    """Worker-grid mesh for shard_map'd flat-buffer runs, host or TPU.

    Builds the (pod, data) worker grid the engine's sync all-reduces over
    — ``(1, W)`` flat or ``(P, W/P)`` hierarchical — and appends a
    trailing ``shard_axis`` of size ``shards`` when row-sharding is on.
    The trailing position makes shard peers mesh-adjacent, so the
    per-shard worker all-reduce never crosses a shard boundary.
    """
    if pods and workers % pods:
        raise ValueError(f"workers {workers} not divisible by pods {pods}")
    shape = (pods, workers // pods) if pods else (1, workers)
    axes = ("pod", "data")
    if shards > 1:
        shape = shape + (shards,)
        axes = axes + (shard_axis,)
    n = math.prod(shape)
    devices = (jax.devices() if devices is None else devices)[:n]
    if len(devices) < n:
        raise ValueError(
            f"engine mesh {shape} needs {n} devices, have {len(devices)} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count={n})")
    return make_mesh(shape, axes, devices=devices)


# TPU v5e hardware constants (per chip) used by the roofline model.
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # B/s
HBM_PER_CHIP = 16 * 2**30    # bytes (v5e: 16 GiB) — the engine-memory
#                              artifact's fit budget
CHIPS_PER_POD = 256          # 16x16 single pod
ICI_LINK_BW = 50e9           # B/s per link (intra-pod)
DCI_LINK_BW = 6.25e9         # B/s per link (cross-pod data-center tier) —
#                              the ~10x-slower tier whose traffic the
#                              hierarchical k2 period amortizes
