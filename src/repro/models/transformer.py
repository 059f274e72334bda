"""Model assembly: embedding -> scan-over-layers blocks -> norm -> logits.

One code path serves all six families:
  dense / vlm / audio : attn + gated MLP blocks
  moe                 : attn + routed-expert MLP (aux loss threaded through scan)
  ssm                 : Mamba2 SSD blocks (no MLP, as in Mamba2)
  hybrid              : parallel attn+SSM block + MLP

Layer params are stacked on a leading "layers" axis and executed with
``lax.scan`` (keeps HLO size O(1) in depth — essential for compiling the
61-layer / 1T-param configs). ``remat=True`` wraps the block in
``jax.checkpoint`` for training.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, hybrid, moe, ssm
from repro.models.layers import mlp, mlp_defs, rms_norm, rms_norm_def
from repro.models.param import ParamDef, materialize, stack_defs
from repro.sharding.constrain import maybe_constrain


# --------------------------------------------------------------------- defs
def layer_defs(cfg: ModelConfig) -> dict:
    d = {}
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        d["attn"] = attention.attention_defs(cfg)
        d["norm1"] = rms_norm_def(cfg.d_model)
        d["norm2"] = rms_norm_def(cfg.d_model)
        if cfg.family == "moe":
            d["moe"] = moe.moe_defs(cfg)
        else:
            d["mlp"] = mlp_defs(cfg)
    elif cfg.family == "ssm":
        d["ssm"] = ssm.ssm_defs(cfg)
        d["norm1"] = rms_norm_def(cfg.d_model)
    elif cfg.family == "hybrid":
        d["hyb"] = hybrid.hybrid_defs(cfg)
        d["norm1"] = rms_norm_def(cfg.d_model)
        d["norm2"] = rms_norm_def(cfg.d_model)
        d["mlp"] = mlp_defs(cfg)
    else:
        raise ValueError(cfg.family)
    return d


def model_defs(cfg: ModelConfig) -> dict:
    defs = {
        "layers": stack_defs(layer_defs(cfg), cfg.num_layers, "layers"),
        "final_norm": rms_norm_def(cfg.d_model),
    }
    if cfg.frontend == "codec":
        defs["frontend_proj"] = ParamDef(
            (cfg.frontend_dim, cfg.d_model), (None, "embed"))
    defs["embed"] = ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                             scale=0.02)
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                   ("embed", "vocab"), scale=0.02)
    return defs


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32):
    return materialize(model_defs(cfg), key, dtype)


# ------------------------------------------------------------------- blocks
def _block(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
           window: Optional[int], contiguous: bool = False):
    """One layer. Returns (x, aux_loss).  ``contiguous``: ``positions`` are
    0..seq-1 (``attention.attend_full``)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        x = x + attention.attend_full(
            cfg, p["attn"], rms_norm(x, p["norm1"], cfg.norm_eps), positions,
            window=window if window is not None else cfg.attn_window,
            contiguous=contiguous)
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if cfg.family == "moe":
            bsz, s, d = h.shape
            y, aux = moe.moe_mlp(cfg, p["moe"], h.reshape(bsz * s, d))
            x = x + y.reshape(bsz, s, d)
        else:
            x = x + mlp(cfg, p["mlp"], h)
    elif cfg.family == "ssm":
        x = x + ssm.ssm_forward(cfg, p["ssm"], rms_norm(x, p["norm1"], cfg.norm_eps))
    elif cfg.family == "hybrid":
        x = x + hybrid.hybrid_forward(
            cfg, p["hyb"], rms_norm(x, p["norm1"], cfg.norm_eps), positions,
            window=window, contiguous=contiguous)
        x = x + mlp(cfg, p["mlp"], rms_norm(x, p["norm2"], cfg.norm_eps))
    if cfg.seq_shard_acts and x.shape[-2] > 1:
        # Megatron-style sequence parallelism: the residual stream lives
        # seq-sharded on the tensor axis; XLA turns the surrounding
        # all-reduces into reduce-scatter + all-gather pairs and runs
        # norms/elementwise on 1/TP of the tokens.
        x = maybe_constrain(x, None, "model", None)
    return x, aux


def embed_inputs(cfg: ModelConfig, params: dict, inputs: jax.Array) -> jax.Array:
    """Token ids (B, S) int -> embeddings; or frontend embeddings pass-through."""
    if cfg.frontend == "codec":
        # stub modality frontend: inputs are precomputed frame embeddings
        return inputs @ params["frontend_proj"]
    return params["embed"][inputs]


@jax.named_scope("head")
def logits_out(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, params["embed"])
    return x @ params["lm_head"]


def forward(cfg: ModelConfig, params: dict, inputs: jax.Array,
            positions: Optional[jax.Array] = None,
            window: Optional[int] = None, remat: bool = False,
            unroll: int = 1, return_hidden: bool = False):
    """Full-sequence forward. Returns (logits, aux_loss) — or the
    post-final-norm hidden states with ``return_hidden`` (for the
    vocab-streaming chunked-CE loss, which never materializes logits)."""
    x = embed_inputs(cfg, params, inputs)
    contiguous = positions is None
    if contiguous:
        positions = jnp.broadcast_to(jnp.arange(x.shape[-2]), x.shape[:-1])

    def body(carry, layer_p):
        h, aux = carry
        h, a = _block(cfg, layer_p, h, positions, window, contiguous)
        return (h, aux + a), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"], unroll=unroll)
    if return_hidden:
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, aux / cfg.num_layers
    return logits_out(cfg, params, x), aux / cfg.num_layers


# ------------------------------------------------------------------- decode
def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16, window: Optional[int] = None):
    """Per-layer caches stacked on a leading layer axis."""
    eff_len = min(cache_len, window) if window is not None else cache_len

    def one_layer():
        if cfg.family == "ssm":
            return ssm.init_ssm_cache(cfg, batch, dtype)
        if cfg.family == "hybrid":
            w = window if window is not None else cfg.attn_window
            alen = min(cache_len, w) if w else cache_len
            return hybrid.init_hybrid_cache(cfg, batch, alen, dtype)
        return attention.init_kv_cache(cfg, batch, eff_len, dtype)

    layer = one_layer()
    return jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf, (cfg.num_layers, *leaf.shape)).copy(),
        layer)


def decode_step(cfg: ModelConfig, params: dict, cache, inputs: jax.Array,
                pos, window: Optional[int] = None, unroll: int = 1):
    """One-token decode against a cache. inputs: (B, 1) ids or (B, 1, F) embeds.

    Returns (logits (B, 1, V), new_cache).
    """
    x = embed_inputs(cfg, params, inputs)

    def body(h, scanned):
        layer_p, layer_c = scanned
        if cfg.family == "ssm":
            y, c = ssm.ssm_decode_step(
                cfg, layer_p["ssm"], rms_norm(h, layer_p["norm1"], cfg.norm_eps),
                layer_c)
            h = h + y
        elif cfg.family == "hybrid":
            y, c = hybrid.hybrid_decode_step(
                cfg, layer_p["hyb"], rms_norm(h, layer_p["norm1"], cfg.norm_eps),
                layer_c, pos, window=window)
            h = h + y
            h = h + mlp(cfg, layer_p["mlp"],
                        rms_norm(h, layer_p["norm2"], cfg.norm_eps))
        else:
            y, c = attention.decode_attend(
                cfg, layer_p["attn"], rms_norm(h, layer_p["norm1"], cfg.norm_eps),
                layer_c, pos,
                window=window if window is not None else cfg.attn_window)
            h = h + y
            hh = rms_norm(h, layer_p["norm2"], cfg.norm_eps)
            if cfg.family == "moe":
                bsz, s, d = hh.shape
                ymoe, _ = moe.moe_mlp(cfg, layer_p["moe"], hh.reshape(bsz * s, d))
                h = h + ymoe.reshape(bsz, s, d)
            else:
                h = h + mlp(cfg, layer_p["mlp"], hh)
        return h, c

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache),
                                unroll=unroll)
    return logits_out(cfg, params, x), new_cache


def prefill(cfg: ModelConfig, params: dict, inputs: jax.Array,
            cache_len: int, window: Optional[int] = None, unroll: int = 1,
            last_only: bool = False):
    """Cache-building prefill: full-sequence forward that also emits the
    decode cache (KV / SSM state / conv window) for every layer, stacked on
    the layer axis by the scan itself.

    Returns (logits (B, S, V), cache) — cache is layout-compatible with
    ``init_cache``/``decode_step``.
    """
    x = embed_inputs(cfg, params, inputs)
    positions = jnp.broadcast_to(jnp.arange(x.shape[-2]), x.shape[:-1])
    eff_window = window if window is not None else cfg.attn_window

    def body(h, layer_p):
        if cfg.family == "ssm":
            y, c = ssm.ssm_forward(
                cfg, layer_p["ssm"], rms_norm(h, layer_p["norm1"], cfg.norm_eps),
                return_cache=True)
            h = h + y
            if cfg.seq_shard_acts and h.shape[-2] > 1:
                h = maybe_constrain(h, None, "model", None)
            return h, c
        if cfg.family == "hybrid":
            y, c = hybrid.hybrid_forward(
                cfg, layer_p["hyb"], rms_norm(h, layer_p["norm1"], cfg.norm_eps),
                positions, window=window, return_cache=True,
                cache_len=cache_len)
            h = h + y
            h = h + mlp(cfg, layer_p["mlp"],
                        rms_norm(h, layer_p["norm2"], cfg.norm_eps))
            return h, c
        y, kv = attention.attend_full(
            cfg, layer_p["attn"], rms_norm(h, layer_p["norm1"], cfg.norm_eps),
            positions, window=eff_window, return_kv=True)
        c = attention.prefill_kv_cache(cfg, kv, cache_len, eff_window, h.dtype)
        h = h + y
        hh = rms_norm(h, layer_p["norm2"], cfg.norm_eps)
        if cfg.family == "moe":
            bsz, s, d = hh.shape
            ymoe, _ = moe.moe_mlp(cfg, layer_p["moe"], hh.reshape(bsz * s, d))
            h = h + ymoe.reshape(bsz, s, d)
        else:
            h = h + mlp(cfg, layer_p["mlp"], hh)
        if cfg.seq_shard_acts and h.shape[-2] > 1:
            h = maybe_constrain(h, None, "model", None)
        return h, c

    x, cache = jax.lax.scan(body, x, params["layers"], unroll=unroll)
    if last_only:
        # serving only needs the next-token distribution: computing logits
        # for every prefill position would be a (B, S, V) tensor — at 32k x
        # 64k-vocab that is ~10^2 GB of matmul + memory for nothing.
        x = x[..., -1:, :]
    return logits_out(cfg, params, x), cache
