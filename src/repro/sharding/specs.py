"""Logical-axis -> PartitionSpec rules.

ParamDef axes (see models/param.py) are mapped onto mesh axes according to
the MeshConfig role assignment. The same rules build specs for worker-stacked
algorithm state (params, Δ, momentum all share the param layout).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import MeshConfig, ModelConfig
from repro.models.param import ParamDef, is_def


def shardings(mesh: Mesh, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def axis_rules(cfg: ModelConfig, mesh_cfg: MeshConfig) -> dict:
    tensor = tuple(mesh_cfg.tensor_axes)
    fsdp = tuple(mesh_cfg.fsdp_axes)
    worker = tuple(mesh_cfg.worker_axes)
    t = mesh_cfg.tensor_size
    experts_sharded = bool(cfg.num_experts) and cfg.num_experts % t == 0
    rules = {
        "layers": None,
        "worker": worker if worker else None,
        "vocab": tensor,
        "embed": fsdp if fsdp else None,
        "heads": tensor if cfg.num_heads and cfg.num_heads % t == 0 else None,
        "kv_heads": tensor if cfg.num_kv_heads and cfg.num_kv_heads % t == 0 else None,
        # expert-parallel: the expert dim takes the tensor axis, so expert
        # (and shared-expert) ff stays unsharded to avoid a duplicate axis.
        "ff": None if experts_sharded else tensor,
        "experts": tensor if experts_sharded else None,
        # expert weights 2D: (experts -> tensor, d -> fsdp); the activation
        # constraint in models/moe.py decides gather-vs-partial-sum by
        # capacity (see EXPERIMENTS.md §Perf pair C).
        "expert_embed": fsdp if fsdp else None,
        "expert_ff": None,
        "ssm_inner": tensor if cfg.ssm_state and cfg.ssm_d_inner % t == 0 else None,
        None: None,
    }
    return rules


def _norm(r):
    """() or None -> None; 1-tuple -> name; n-tuple stays a tuple."""
    if not r:
        return None
    if isinstance(r, tuple) and len(r) == 1:
        return r[0]
    return r


def spec_for(d: ParamDef, rules: dict) -> P:
    return P(*[_norm(rules.get(ax, None)) for ax in d.axes])


def partition_specs(defs, cfg: ModelConfig, mesh_cfg: MeshConfig):
    """Pytree of PartitionSpec mirroring a ParamDef pytree."""
    rules = axis_rules(cfg, mesh_cfg)
    return jax.tree.map(lambda d: spec_for(d, rules), defs, is_leaf=is_def)


def worker_stacked_spec(spec: P, mesh_cfg: MeshConfig) -> P:
    """Prepend the worker axis to an existing spec."""
    return P(_norm(tuple(mesh_cfg.worker_axes)), *spec)


def engine_shard_axis(mesh_cfg: MeshConfig, ecfg) -> Optional[str]:
    """Resolve the engine-state row-shard axis against a MeshConfig.

    The flat-buffer engine shards every (W, R, C) buffer's row dim over
    ``ecfg.shard_axis`` (``EngineConfig``); on the production mesh that
    axis REUSES the tensor axis "model" — engine rows and model tensor
    dims shard over the same devices, so neither replicates across the
    other's axis.  Returns None when sharding is off (``shards <= 1``) or
    the mesh simply lacks the axis (host smoke meshes), and raises when
    the axis exists at the WRONG size — a silent half-shard would desync
    the per-shard all-reduce.
    """
    if getattr(ecfg, "shards", 1) <= 1:
        return None
    sizes = dict(zip(mesh_cfg.axis_names, mesh_cfg.shape))
    ax = ecfg.shard_axis
    if ax not in sizes:
        return None
    if sizes[ax] != ecfg.shards:
        raise ValueError(
            f"engine shards={ecfg.shards} but mesh axis {ax!r} has size "
            f"{sizes[ax]} — the row-shard count must equal the mesh axis "
            f"backing it")
    return ax


def batch_spec(mesh_cfg: MeshConfig, *, worker_stacked: bool, extra_dims: int) -> P:
    """Spec for (W, local_batch, ...) train batches or (batch, ...) serve."""
    w = tuple(mesh_cfg.worker_axes)
    f = tuple(mesh_cfg.fsdp_axes)
    if worker_stacked:
        return P(_norm(w), _norm(f), *([None] * extra_dims))
    # serving: batch over all data-like axes
    return P(_norm(w + f), *([None] * extra_dims))


from repro.sharding.constrain import maybe_constrain  # noqa: F401,E402
