"""shard_map MoE dispatch — the structural fix for the GSPMD limitation
measured in EXPERIMENTS.md §Perf pair C.

GSPMD cannot partition the sort/scatter dispatch against 2D-sharded expert
weights (it replicates via "involuntary full rematerialization"). Under
``shard_map`` the dispatch is LOCAL by construction:

  mesh axes: tokens sharded over "data", experts sharded over "model",
  expert weights stored 2D-sharded (E -> model, d -> data).

  per (data j, model i) device:
    1. all_gather its expert shard's weights over "data"  (FSDP gather,
       ~2.1 GiB/layer on kimi-k2 — amortizable/overlappable)
    2. route its LOCAL tokens; keep only assignments to its LOCAL experts
       (expected T_loc * k / model_size of them)
    3. sort/scatter dispatch entirely locally (no cross-shard scatter!)
    4. psum the partial outputs over "model" (each token's k experts live
       on specific shards)  — (T_loc, d) bf16 per layer.

Per-layer collective bytes on kimi-k2 train_4k (T_loc = 65536):
  3 x 2.1 GiB weight AG + 0.94 GiB psum  ≈ 3 GiB  vs the GSPMD baseline's
  ~127 GiB of hidden-state all-reduce — the napkin ~40x reduction that the
  §Perf pair-C iterations could not reach with constraint steering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import moe as moe_ref


def _local_dispatch_compute(cfg: ModelConfig, x: jax.Array,
                            weights: jax.Array, ids: jax.Array,
                            wg: jax.Array, wu: jax.Array, wd: jax.Array,
                            e_loc: int, shard: jax.Array) -> jax.Array:
    """Dispatch the local tokens to this shard's e_loc experts and compute.

    x: (T_loc, d); weights/ids: (T_loc, k) GLOBAL routing decisions;
    wg/wu: (e_loc, d, ff); wd: (e_loc, ff, d). Returns the PARTIAL output
    (T_loc, d) covering only the local experts (psum over "model" outside).
    """
    t, d = x.shape
    k = cfg.experts_per_token
    cap = moe_ref.capacity(cfg, t)

    flat_e = ids.reshape(-1)
    is_local = (flat_e // e_loc) == shard
    local_e = jnp.where(is_local, flat_e - shard * e_loc, e_loc)  # e_loc = drop

    order = jnp.argsort(local_e)                      # non-local sort last
    sorted_e = local_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(t * k) - first
    keep = (sorted_e < e_loc) & (pos < cap)
    token = order // k

    safe_e = jnp.where(keep, sorted_e, 0)
    safe_pos = jnp.where(keep, pos, cap - 1)
    xk = x[token] * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((e_loc, cap, d), x.dtype).at[safe_e, safe_pos].add(xk)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd)

    contrib = out_buf[safe_e, safe_pos] * keep[:, None].astype(x.dtype)
    w = weights.reshape(-1)[order].astype(x.dtype)
    return jnp.zeros((t, d), x.dtype).at[token].add(contrib * w[:, None])


def moe_mlp_shardmap(cfg: ModelConfig, p: dict, x: jax.Array, mesh,
                     data_axis: str = "data", model_axis: str = "model"):
    """Drop-in MoE layer under explicit shard_map.

    x: (T, d) global; expert weights 2D-sharded (E->model, d->data);
    router replicated. Returns (y (T, d), aux).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    e_loc = cfg.num_experts // sizes[model_axis]

    def block(x_loc, router, wg, wu, wd):
        # weights arrive d-sharded: FSDP-gather over the data axis
        wg = jax.lax.all_gather(wg, data_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, data_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, data_axis, axis=2, tiled=True)
        weights, ids, aux = moe_ref.route(cfg, router, x_loc)
        shard = jax.lax.axis_index(model_axis)
        y_part = _local_dispatch_compute(cfg, x_loc, weights, ids,
                                         wg, wu, wd, e_loc, shard)
        y = jax.lax.psum(y_part, model_axis)
        aux = jax.lax.pmean(aux, data_axis)
        return y, aux

    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(data_axis, None),            # tokens
                  P(None, None),                 # router (replicated)
                  P(model_axis, data_axis, None),  # w_gate
                  P(model_axis, data_axis, None),  # w_up
                  P(model_axis, None, data_axis)),  # w_down
        out_specs=(P(data_axis, None), P()),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
