"""Hymba-style hybrid block: attention heads and SSM heads run in parallel
on the same input, their (individually normalized) outputs are averaged.
[arXiv:2411.13676 §2]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, ssm
from repro.models.layers import rms_norm, rms_norm_def
from repro.models.param import ParamDef


def hybrid_defs(cfg: ModelConfig) -> dict:
    return {
        "attn": attention.attention_defs(cfg),
        "ssm": ssm.ssm_defs(cfg),
        "attn_out_norm": rms_norm_def(cfg.d_model),
        "ssm_out_norm": rms_norm_def(cfg.d_model),
        # learnable fusion scale (Hymba's beta)
        "fuse_beta": ParamDef((2,), (None,), init="ones"),
    }


def _fuse(p: dict, ya: jax.Array, ys: jax.Array, eps: float) -> jax.Array:
    ya = rms_norm(ya, p["attn_out_norm"], eps)
    ys = rms_norm(ys, p["ssm_out_norm"], eps)
    beta = p["fuse_beta"].astype(ya.dtype)
    return 0.5 * (beta[0] * ya + beta[1] * ys)


def hybrid_forward(cfg: ModelConfig, p: dict, x: jax.Array,
                   positions: jax.Array, window=None,
                   return_cache: bool = False, cache_len: int = 0,
                   contiguous: bool = False):
    w = window if window is not None else cfg.attn_window
    ya = attention.attend_full(cfg, p["attn"], x, positions, window=w,
                               return_kv=return_cache, contiguous=contiguous)
    ys = ssm.ssm_forward(cfg, p["ssm"], x, return_cache=return_cache)
    if return_cache:
        ya, kv = ya
        ys, ssm_cache = ys
        alen = min(cache_len, w) if w else cache_len
        attn_cache = attention.prefill_kv_cache(cfg, kv, alen, w, x.dtype)
        return _fuse(p, ya, ys, cfg.norm_eps), {"attn": attn_cache,
                                                "ssm": ssm_cache}
    return _fuse(p, ya, ys, cfg.norm_eps)


def init_hybrid_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    return {
        "attn": attention.init_kv_cache(cfg, batch, cache_len, dtype),
        "ssm": ssm.init_ssm_cache(cfg, batch, dtype),
    }


def hybrid_decode_step(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                       pos, window=None):
    w = window if window is not None else cfg.attn_window
    ya, kv = attention.decode_attend(cfg, p["attn"], x, cache["attn"], pos, window=w)
    ys, st = ssm.ssm_decode_step(cfg, p["ssm"], x, cache["ssm"])
    return _fuse(p, ya, ys, cfg.norm_eps), {"attn": kv, "ssm": st}
