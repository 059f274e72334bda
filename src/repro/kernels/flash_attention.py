"""Causal (or windowed) flash attention, forward and backward, on the TPU.

A thin wrapper around JAX's Pallas splash-attention kernels
(``jax.experimental.pallas.ops.tpu.splash_attention``): one forward kernel
that saves the row log-sum-exp, and one fused kernel for the backward that
gives dq, dk and dv, through the kernel's own ``custom_vjp``.  What the
wrapper adds:

  * the mask: ``CausalMask`` for every head, or ``LocalMask`` for a sliding
    window (a key ``window`` or more positions behind its query is masked);
    splash skips every block the mask leaves empty;
  * grouped-query heads as they are: q is (H, S, D), k and v (KVH, S, D),
    and each q head reads kv head ``h // (H // KVH)``, so no kv head is
    repeated in memory;
  * the sequence padded to a multiple of the block (128 at the least).
    Causal masking keeps every padded key out of every real query, and the
    padded query rows are sliced off;
  * the softmax scale, applied to q in q's dtype (splash takes q pre-scaled).

The kernels take the operands in the dtype they are given and round nothing
themselves: softmax statistics and the dq/dk/dv accumulators are fp32
inside, the output is q's dtype.  The kernels keep their names
(``splash_mha_fwd_residuals``, ``splash_mha_dkv_no_residuals``), which is
how a profile shows them.

``interpret=True`` runs the kernel bodies in Python (the CPU tests).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

LANES = 128         # splash's blocks are multiples of the lane width


def block_sizes(seq: int, head_dim: int) -> tuple[int, int]:
    """(block_q, block_kv) for a padded sequence of ``seq`` (a multiple of
    128) at ``head_dim``: the largest of 512, 256, 128 that divides
    ``seq``.  On a v5e at head_dim 64 (forward, remat and backward), 512
    was the fastest block at 512 tokens and within 4% of 1024 at 2048
    tokens, where 128 ran 4.9x slower; the fused dq+dkv backward beat
    separate dq and dkv kernels at every block size tried."""
    del head_dim
    block = next(b for b in (512, 256, LANES) if seq % b == 0)
    return block, block


@functools.lru_cache(maxsize=32)
def _kernel(heads: int, seq: int, causal: bool, window: Optional[int],
            block_q: int, block_k: int, interpret: bool):
    if window is not None:
        head_mask = splash.LocalMask(
            (seq, seq), window_size=(window - 1, 0 if causal else None),
            offset=0)
    elif causal:
        head_mask = splash.CausalMask((seq, seq))
    else:
        head_mask = splash.FullMask((seq, seq))
    sizes = splash.BlockSizes(
        block_q=block_q, block_kv=block_k, block_kv_compute=block_k,
        block_q_dkv=block_q, block_kv_dkv=block_k,
        block_kv_dkv_compute=block_k, use_fused_bwd_kernel=True)
    # the mask tables are concrete arrays, kept across traces
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            splash.MultiHeadMask([head_mask] * heads), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = True) -> jax.Array:
    """q: (H, S, D); k, v: (KVH, S, D) with H a multiple of KVH -> (H, S, D).

    ``scale`` defaults to ``D ** -0.5``.  Blocks default to
    ``block_sizes``; both must be multiples of 128.  Any S works when
    ``causal`` (the wrapper pads); without it S must be a multiple of
    both blocks.
    """
    h, s, d = q.shape
    auto_q, auto_k = block_sizes(-(-s // LANES) * LANES, d)
    block_q = block_q or auto_q
    block_k = block_k or auto_k
    if block_q % LANES or block_k % LANES:
        raise ValueError(f"blocks must be multiples of {LANES}: "
                         f"{block_q}, {block_k}")
    pad = (-s) % math.lcm(block_q, block_k)
    if pad and not causal:
        raise ValueError(f"a non-causal sequence of {s} is not a multiple "
                         f"of the blocks {block_q}, {block_k}")
    scale = d ** -0.5 if scale is None else scale
    if scale != 1.0:
        q = q * jnp.asarray(scale, q.dtype)
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(t, widths) for t in (q, k, v))
    kernel = _kernel(h, s + pad, causal, window, block_q, block_k,
                     interpret)
    return kernel(q, k, v)[:, :s]
