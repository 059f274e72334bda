"""jit'd user-facing wrappers around the Pallas kernels.

These handle layout munging (head flattening, padding to block multiples,
pytree flattening for the optimizer kernels) so callers use natural shapes.
``interpret`` defaults to True on CPU (kernel body runs in Python for
correctness validation) and False on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan as ssd
from repro.kernels import vrl_update as vu


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------ attention op
def mha_flash(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None,
              block_q: Optional[int] = None, block_k: Optional[int] = None,
              interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, S, H, D); k, v: (B, S, KVH, D) -> (B, S, H, D).

    Grouped-query heads stay as they are (q head h reads kv head
    h // (H // KVH)); the kernel pads S to its block.  ``scale`` defaults to
    D ** -0.5; blocks to ``flash_attention.block_sizes``.
    """
    if interpret is None:
        interpret = _default_interpret()
    one = functools.partial(fa.flash_attention, causal=causal, window=window,
                            scale=scale, block_q=block_q, block_k=block_k,
                            interpret=interpret)
    out = jax.vmap(one)(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v)))
    return jnp.swapaxes(out, 1, 2)


# ------------------------------------------------------------------ ssd op
def ssd_chunk_scan(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                   b: jax.Array, c: jax.Array, *, chunk: int = 256,
                   interpret: Optional[bool] = None) -> jax.Array:
    """x: (B, L, H, P); dt: (B, L, H); a_log: (H,); b, c: (B, L, N)."""
    if interpret is None:
        interpret = _default_interpret()
    bsz, l, h, p = x.shape
    chunk = min(chunk, l)
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    lp = l + pad
    xt = jnp.moveaxis(x, 2, 1).reshape(bsz * h, lp, p)
    dtt = jnp.moveaxis(dt, 2, 1).reshape(bsz * h, lp)
    alog = jnp.tile(a_log[None, :], (bsz, 1)).reshape(bsz * h, 1)
    y = ssd.ssd_scan(xt, dtt, alog, b, c, chunk=chunk, num_heads=h,
                     interpret=interpret)
    y = y[:, :l].reshape(bsz, h, l, p)
    return jnp.moveaxis(y, 1, 2)


# ------------------------------------------------- fused optimizer updates
_LANES = 256


def _leaf_tile(n: int, block: int) -> tuple[int, int]:
    """(block, lanes) for an n-element leaf: auto block unless forced.

    Auto mode pads rows toward 1024-row multiples but caps padding waste
    (core.flat.choose_block) — a 4 KiB bias vector no longer pads to a
    megabyte tile the way the old hardcoded block did.
    """
    from repro.core.flat import choose_block
    rows = -(-n // _LANES)
    return (block or choose_block(rows)), _LANES


def _to_2d(x: jax.Array, block: int, c: int = _LANES):
    flat = x.reshape(-1)
    pad = (-flat.size) % (c * block)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, c), x.shape, pad


def vrl_local_update_tree(params, grads, delta, *, lr: float,
                          block: int = 0,
                          interpret: Optional[bool] = None):
    """Fused p' = p − γ(g − Δ) over a whole pytree.

    ``block=0`` auto-sizes the per-leaf tile; pass an explicit block (and
    ``interpret``) to pin the layout — both are surfaced through
    ``configs.base.EngineConfig`` for the flat-buffer engine, which is the
    preferred path (one kernel for the whole model instead of one per leaf).
    """
    if interpret is None:
        interpret = _default_interpret()

    def one(p, g, d):
        blk, c = _leaf_tile(p.size, block)
        p2, shp, _ = _to_2d(p, blk, c)
        g2, _, _ = _to_2d(g, blk, c)
        d2, _, _ = _to_2d(d.astype(p.dtype), blk, c)
        out = vu.vrl_local_update(p2, g2, d2, lr=lr, block=blk,
                                  interpret=interpret)
        return out.reshape(-1)[:p.size].reshape(shp)

    return jax.tree.map(one, params, grads, delta)


def vrl_sync_update_tree(params, xbar, delta, *, k: int, lr: float,
                         block: int = 0,
                         interpret: Optional[bool] = None):
    """Fused Δ' = Δ + (x̂−p)/(kγ); p' = x̂ over a whole pytree.

    Tiling as in ``vrl_local_update_tree`` (auto unless ``block`` given).
    """
    if interpret is None:
        interpret = _default_interpret()
    inv_kg = 1.0 / (k * lr)

    def one(p, xb, d):
        blk, c = _leaf_tile(p.size, block)
        p2, shp, _ = _to_2d(p, blk, c)
        x2, _, _ = _to_2d(jnp.broadcast_to(xb, p.shape), blk, c)
        d2, dshp, _ = _to_2d(d, blk, c)
        po, do = vu.vrl_sync_update(p2, x2, d2, inv_kg=inv_kg, block=blk,
                                    interpret=interpret)
        return (po.reshape(-1)[:p.size].reshape(shp),
                do.reshape(-1)[:d.size].reshape(dshp))

    outs = jax.tree.map(one, params, xbar, delta)
    new_p = jax.tree.map(lambda t: t[0], outs,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_d = jax.tree.map(lambda t: t[1], outs,
                         is_leaf=lambda t: isinstance(t, tuple))
    return new_p, new_d
