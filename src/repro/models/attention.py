"""GQA attention: training/prefill forward and KV-cache decode.

Cache layouts
  full window : k/v (batch, seq_len, kv_heads, head_dim), append at position
  sliding     : same shape with seq_len = window, ring-buffer writes

Training attention at positions 0..S-1 on a TPU runs the causal flash
kernel (``kernels.flash_attention``): bf16 operands, fp32 softmax
statistics and accumulators.  Everywhere else (the CPU backend, explicit
positions, decode): QK^T and softmax in fp32, PV in input dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.layers import apply_rope
from repro.models.param import ParamDef

NEG_INF = -1e30

# Shortest sequence the flash kernel runs at.  On a v5e (qwen2-0.5b heads,
# 24 layers' forward, remat and backward in one program) the dense core took
# 0.10 ms a layer at 512 tokens against the kernel's 0.14 at its best
# blocks; at 2048 tokens 8.2 ms against 1.8.
FLASH_MIN_SEQ = 1024


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


def _dense_core(positions: jax.Array, window: Optional[int], group: int,
                scale: float, dtype, q, k, v):
    """(..., S, H, D) q against (..., S, KVH, D) k, v: fp32 scores, the
    position mask, softmax and P·V in ``dtype``, on the repeated kv heads."""
    k = _repeat_kv(k, group)
    v = _repeat_kv(v, group)
    # the (..., H, S, S) part: scores, mask, softmax and P·V
    with jax.named_scope("attention"):
        scores = jnp.einsum("...qhk,...shk->...hqs", q, k
                            ).astype(jnp.float32) * scale
        qi = positions[..., None, :, None]   # (..., 1, q, 1)
        ki = positions[..., None, None, :]   # (..., 1, 1, s)
        mask = ki <= qi                  # (..., 1, q, s) broadcast over heads
        if window is not None:
            mask = mask & (ki > qi - window)
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("...hqs,...shk->...qhk", probs, v)


def _flash_core(window: Optional[int], scale: float, dtype, q, k, v):
    """The same attention at positions 0..S-1 through the flash kernel.

    q is scaled in fp32, then q, k, v are rounded to bf16: the rounding
    XLA's DEFAULT precision gives the dense path's fp32 products on the
    chip.  Softmax statistics and accumulators stay fp32 in the kernel."""
    lead = q.shape[:-3]
    with jax.named_scope("attention"):
        q, k, v = (t.reshape(-1, *t.shape[-3:]).astype(jnp.bfloat16)
                   for t in (q * scale, k, v))
        out = ops.mha_flash(q, k, v, window=window, scale=1.0,
                            interpret=False)
        return out.astype(dtype).reshape(*lead, *out.shape[1:])


def attend_full(cfg: ModelConfig, p: dict, x: jax.Array,
                positions: jax.Array,
                window: Optional[int] = None,
                return_kv: bool = False,
                contiguous: bool = False):
    """Training / prefill attention over a full sequence.

    x: (..., seq, d_model); positions: (..., seq) absolute positions.
    With ``return_kv`` also returns the roped (k, v) for cache prefill.

    ``contiguous`` says that ``positions`` are 0..seq-1 in every row.  Then,
    from ``FLASH_MIN_SEQ`` tokens on, a TPU lowering runs the causal flash
    kernel (``kernels.flash_attention``, forward and backward); every other
    lowering, shorter sequence, and call with other positions runs the
    dense fp32-score path.
    """
    group = cfg.num_heads // cfg.num_kv_heads
    q, k, v = _project_qkv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    kv_cache = (k, v) if return_kv else None

    scale = cfg.head_dim ** -0.5
    dense = functools.partial(_dense_core, positions, window, group, scale,
                              x.dtype)
    if contiguous and x.shape[-2] >= FLASH_MIN_SEQ:
        out = jax.lax.platform_dependent(
            q, k, v, default=dense,
            tpu=functools.partial(_flash_core, window, scale, x.dtype))
    else:
        out = dense(q, k, v)
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
