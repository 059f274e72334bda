"""GQA attention: training/prefill forward and KV-cache decode.

Cache layouts
  full window : k/v (batch, seq_len, kv_heads, head_dim), append at position
  sliding     : same shape with seq_len = window, ring-buffer writes

Numerics: QK^T and softmax in fp32, PV in input dtype.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope
from repro.models.param import ParamDef

NEG_INF = -1e30


def attention_defs(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # explicit fan-in scales: the default reads fan-in from dim -2, which
    # here is a head count (q/k/v) or head_dim (o), not the contraction
    s_in, s_out = d ** -0.5, (h * hd) ** -0.5
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", None), scale=s_in),
        "wk": ParamDef((d, kv, hd), ("embed", "kv_heads", None), scale=s_in),
        "wv": ParamDef((d, kv, hd), ("embed", "kv_heads", None), scale=s_in),
        "wo": ParamDef((h, hd, d), ("heads", None, "embed"), scale=s_out),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, hd), ("heads", None), init="zeros")
        defs["bk"] = ParamDef((kv, hd), ("kv_heads", None), init="zeros")
        defs["bv"] = ParamDef((kv, hd), ("kv_heads", None), init="zeros")
    return defs


def _project_qkv(cfg: ModelConfig, p: dict, x: jax.Array):
    q = jnp.einsum("...sd,dhk->...shk", x, p["wq"])
    k = jnp.einsum("...sd,dhk->...shk", x, p["wk"])
    v = jnp.einsum("...sd,dhk->...shk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def _repeat_kv(x: jax.Array, group: int) -> jax.Array:
    """(..., s, kv, hd) -> (..., s, kv*group, hd)"""
    if group == 1:
        return x
    return jnp.repeat(x, group, axis=-2)


def attend_full(cfg: ModelConfig, p: dict, x: jax.Array,
                positions: jax.Array,
                window: Optional[int] = None,
                return_kv: bool = False):
    """Training / prefill attention over a full sequence.

    x: (..., seq, d_model); positions: (..., seq) absolute positions.
    With ``return_kv`` also returns the roped (k, v) for cache prefill.
    """
    group = cfg.num_heads // cfg.num_kv_heads
    q, k, v = _project_qkv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    kv_cache = (k, v) if return_kv else None
    k = _repeat_kv(k, group)
    v = _repeat_kv(v, group)

    # the (..., H, S, S) part: scores, mask, softmax and P·V
    with jax.named_scope("attention"):
        scale = cfg.head_dim ** -0.5
        scores = jnp.einsum("...qhk,...shk->...hqs", q, k
                            ).astype(jnp.float32) * scale
        qi = positions[..., None, :, None]   # (..., 1, q, 1)
        ki = positions[..., None, None, :]   # (..., 1, 1, s)
        mask = ki <= qi                  # (..., 1, q, s) broadcast over heads
        if window is not None:
            mask = mask & (ki > qi - window)
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("...hqs,...shk->...qhk", probs, v)
    out = jnp.einsum("...qhk,hkd->...qd", out, p["wo"])
    if return_kv:
        return out, kv_cache
    return out


def prefill_kv_cache(cfg: ModelConfig, kv, cache_len: int,
                     window: Optional[int], dtype):
    """Build a decode cache from prefill (k, v): (b, s, kvh, hd).

    For windowed attention the cache is a ring buffer of size ``window``
    whose slot layout matches ``decode_attend`` (slot = pos % window).
    """
    k, v = kv
    b, s = k.shape[0], k.shape[1]
    if window is not None:
        cache = init_kv_cache(cfg, b, window, dtype)
        take = min(window, s)
        pos = jnp.arange(s - take, s)
        slots = pos % window
        ck = cache["k"].at[:, slots].set(k[:, s - take:].astype(dtype))
        cv = cache["v"].at[:, slots].set(v[:, s - take:].astype(dtype))
        return {"k": ck, "v": cv}
    cache = init_kv_cache(cfg, b, cache_len, dtype)
    ck = cache["k"].at[:, :s].set(k.astype(dtype))
    cv = cache["v"].at[:, :s].set(v.astype(dtype))
    return {"k": ck, "v": cv}


# ------------------------------------------------------------------ decode
def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, cache_len, kv, hd), dtype),
        "v": jnp.zeros((batch, cache_len, kv, hd), dtype),
    }


def decode_attend(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                  pos: jax.Array, window: Optional[int] = None):
    """One-token decode. x: (batch, 1, d); pos: scalar current position.

    Returns (out (batch, 1, d), new_cache). The cache holds positions
    [0, cache_len) for full attention, or a ring buffer of the last
    ``window`` positions when ``window`` is set (cache_len == window).
    """
    group = cfg.num_heads // cfg.num_kv_heads
    cache_len = cache["k"].shape[1]
    q, k, v = _project_qkv(cfg, p, x)                 # (b, 1, h/kv, hd)
    posv = jnp.full(x.shape[:-2] + (1,), pos, jnp.int32)
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)

    slot = pos % cache_len if window is not None else pos
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)

    scale = cfg.head_dim ** -0.5
    # (b, kv, g, hd) x (b, s, kv, hd) -> (b, kv, g, s)
    qh = q[:, 0].reshape(q.shape[0], cfg.num_kv_heads, group, cfg.head_dim)
    scores = jnp.einsum("bkgh,bskh->bkgs", qh, ck).astype(jnp.float32) * scale
    sidx = jnp.arange(cache_len)
    if window is not None:
        # ring buffer: slot s holds absolute position p' with p' % W == s,
        # the latest such p' <= pos:
        abs_pos = pos - ((pos - sidx) % cache_len)
        valid = (abs_pos >= 0) & (abs_pos <= pos) & (abs_pos > pos - window)
    else:
        valid = sidx <= pos
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, cv)
    out = out.reshape(x.shape[0], 1, cfg.num_heads, cfg.head_dim)
    out = jnp.einsum("bqhk,hkd->bqd", out, p["wo"])
    return out, {"k": ck, "v": cv}
