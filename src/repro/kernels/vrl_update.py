"""Fused VRL-SGD update kernels (the paper's eq. 4-6 as single HBM passes).

The paper's math is elementwise over model-sized buffers, so on TPU it is
purely HBM-bandwidth-bound. Unfused, the local step reads p, g, Δ and writes
v then p (5 model-sized transfers); the fused kernel reads 3 and writes 1.
The sync step fuses the Δ update with the parameter broadcast the same way.

  local:  p' = p − γ·(g − Δ)                          (eq. 5 + 6)
  sync:   Δ' = Δ + (x̂ − p)/(kγ);  p' = x̂             (eq. 4 + line 6)

Two families live here:

  * ``vrl_local_update`` / ``vrl_sync_update`` — the original per-leaf 2D
    tile kernels (used by ``ops.py``'s tree wrappers and their tests).
  * ``fused_local_{sgd,momentum,adam}`` / ``fused_sync_vrl`` /
    ``fused_sync_easgd`` — the engine's worker-stacked (W, R, C) kernels.
    One grid step per (worker, row-tile); the inner-optimizer moment update
    is fused into the same HBM pass, and dynamic scalars (Adam bias
    correction, the sync-time k_eff·γ) ride in as a tiny (1, n) operand so
    the compiled kernel never retraces per step.  All math is fp32
    in-register with per-buffer output casts, matching the reference tree
    path bit-for-bit in fp32.
  * ``fused_hier_local_{sgd,momentum,adam}`` / ``fused_sync_hier{1,2}`` —
    the two-level hierarchical engine's pod-major (P, D, R, C) kernels.
    The local step subtracts BOTH corrections (v = g − Δ1 − Δ2) in the same
    pass; Δ2 is carried as a per-pod (P, 1, R, C) buffer whose blocks are
    broadcast over the intra-pod axis by the index map, never materialized
    at (P, D) size in HBM.

State buffers are donated (``input_output_aliases``) so every update is
in-place: the kernels read each block exactly once before overwriting it,
and XLA falls back to a copy when a donated buffer has another consumer.

Every ``pallas_call`` is named (``vrl_local_sgd``, ``vrl_sync``, ...): the
compiled custom call takes that name, so a profile names the kernel rather
than the jitted function around it.

``block``/``interpret`` come from the engine config (``configs.base
.EngineConfig``); the (R, C) layout and auto block choice from
``core/flat.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.comm import compressors as cc


def default_interpret() -> bool:
    """Interpret-mode (python body) everywhere Pallas cannot compile —
    i.e. anything but real TPU/GPU backends.  Interpret mode is orders of
    magnitude slower than compiled code; ``update_backend="auto"`` picks
    the XLA executor (``kernels/xla_update``) on such backends instead."""
    return jax.default_backend() not in ("tpu", "gpu")


def _local_kernel(p_ref, g_ref, d_ref, o_ref, *, lr: float):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    o_ref[...] = (p - lr * (g - d)).astype(o_ref.dtype)


def _sync_kernel(p_ref, xbar_ref, d_ref, po_ref, do_ref, *, inv_kg: float):
    p = p_ref[...].astype(jnp.float32)
    xb = xbar_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    do_ref[...] = (d + (xb - p) * inv_kg).astype(do_ref.dtype)
    po_ref[...] = xb.astype(po_ref.dtype)


@functools.partial(jax.jit, static_argnames=("lr", "block", "interpret"))
def vrl_local_update(p: jax.Array, g: jax.Array, delta: jax.Array, *,
                     lr: float, block: int = 1024,
                     interpret: bool = True) -> jax.Array:
    """p, g, delta: (R, C) with R % block == 0 -> updated p."""
    r, c = p.shape
    assert r % block == 0, (r, block)
    spec = pl.BlockSpec((block, c), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_local_kernel, lr=lr),
        grid=(r // block,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, c), p.dtype),
        name="vrl_local_update",
        interpret=interpret,
    )(p, g, delta)


@functools.partial(jax.jit, static_argnames=("inv_kg", "block", "interpret"))
def vrl_sync_update(p: jax.Array, xbar: jax.Array, delta: jax.Array, *,
                    inv_kg: float, block: int = 1024,
                    interpret: bool = True):
    """Returns (p', Δ')."""
    r, c = p.shape
    assert r % block == 0, (r, block)
    spec = pl.BlockSpec((block, c), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_sync_kernel, inv_kg=inv_kg),
        grid=(r // block,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((r, c), p.dtype),
                   jax.ShapeDtypeStruct((r, c), delta.dtype)],
        name="vrl_sync_update",
        interpret=interpret,
    )(p, xbar, delta)


# ===================================================================== engine
# Worker-stacked (W, R, C) kernels for core/engine.py.  Grid = (W, R/block);
# every buffer streams through VMEM exactly once per step.

# Bytes the double-buffered row tiles of one kernel may take in VMEM: Mosaic
# scopes 16 MiB of a v5e core's VMEM to a kernel by default, and the rest is
# left for the fp32 temporaries of the kernel body.  At block 1024 x 256
# fp32 lanes, six streamed operands fit; Adam's eight do not.
_VMEM_BUDGET = 12 * 2**20


def _row_block(block: int, *bufs) -> int:
    """Tile height for a kernel over ``bufs`` (every operand and result
    tiled by rows): the largest divisor of ``block`` — a multiple of 8, or
    ``block`` itself — whose double-buffered tiles fit ``_VMEM_BUDGET``.

    The flat layout (``FlatSpec.block``) stays as it is; only the grid gets
    finer, which the elementwise and per-row math cannot see.  A lane dim
    narrower than 128 still takes a full 128-lane tile row."""
    row = sum(-(-b.shape[-1] // 128) * 128 * jnp.dtype(b.dtype).itemsize
              for b in bufs)
    cands = [t for t in range(block, 0, -1)
             if block % t == 0 and (t == block or t % 8 == 0)]
    return next((t for t in cands if 2 * t * row <= _VMEM_BUDGET),
                cands[-1])


def _grid_specs(w: int, r: int, c: int, block: int, n: int):
    """n identical (1, block, C) specs over a (W, R/block) grid."""
    del w, r
    return [pl.BlockSpec((1, block, c), lambda wi, i: (wi, i, 0))
            for _ in range(n)]


def _scal_spec(n: int):
    """(1, n) fp32 dynamic-scalar operand, same tile for every grid step."""
    return pl.BlockSpec((1, n), lambda wi, i: (0, 0))


def _f32(ref):
    return ref[...].astype(jnp.float32)


def _correction(refs, start: int, use_delta: bool, use_bias: bool):
    """v = g − [Δ] − [B] for the local kernels: the optional corrections sit
    at ``refs[start:]`` in (Δ, B) order.  Returns (v, next ref index)."""
    v = _f32(refs[1])
    i = start
    if use_delta:
        v = v - _f32(refs[i])
        i += 1
    if use_bias:
        v = v - _f32(refs[i])
        i += 1
    return v, i


def _fused_sgd_kernel(*refs, lr, wd, use_delta, use_bias):
    v, _ = _correction(refs, 2, use_delta, use_bias)
    p = _f32(refs[0])
    if wd:
        v = v + wd * p
    o_ref = refs[-1]
    o_ref[...] = (p - lr * v).astype(o_ref.dtype)


def fused_local_sgd(p, g, d=None, *, lr: float, wd: float = 0.0,
                    block: int = 1024, interpret=None, b=None):
    """p' = p − γ((g − Δ − B) + wd·p) on (W, R, C) buffers.

    d=None ⇒ Δ ≡ 0; b (BVR-L-SGD's bias variate) =None ⇒ B ≡ 0."""
    if interpret is None:
        interpret = default_interpret()
    w, r, c = p.shape
    use_delta, use_bias = d is not None, b is not None
    ins = (p, g) + ((d,) if use_delta else ()) + ((b,) if use_bias else ())
    block = _row_block(block, *ins, p)
    specs = _grid_specs(w, r, c, block, len(ins))
    return pl.pallas_call(
        functools.partial(_fused_sgd_kernel, lr=lr, wd=wd,
                          use_delta=use_delta, use_bias=use_bias),
        grid=(w, r // block),
        in_specs=specs,
        out_specs=specs[0],
        out_shape=jax.ShapeDtypeStruct((w, r, c), p.dtype),
        input_output_aliases={0: 0},
        name="vrl_local_sgd",
        interpret=interpret,
    )(*ins)


def _fused_momentum_kernel(*refs, lr, beta, wd, nesterov, use_delta,
                           use_bias):
    v, i = _correction(refs, 2, use_delta, use_bias)
    m_ref, po_ref, mo_ref = refs[i], refs[-2], refs[-1]
    p = _f32(refs[0])
    if wd:
        v = v + wd * p
    m_new = beta * _f32(m_ref) + v
    step_dir = v + beta * m_new if nesterov else m_new
    po_ref[...] = (p - lr * step_dir).astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)


def fused_local_momentum(p, g, d, m, *, lr: float, beta: float,
                         wd: float = 0.0, nesterov: bool = False,
                         block: int = 1024, interpret=None, b=None):
    """Momentum inner step fused with the corrections; returns (p', m')."""
    if interpret is None:
        interpret = default_interpret()
    w, r, c = p.shape
    use_delta, use_bias = d is not None, b is not None
    ins = ((p, g) + ((d,) if use_delta else ())
           + ((b,) if use_bias else ()) + (m,))
    block = _row_block(block, *ins, p, m)
    specs = _grid_specs(w, r, c, block, len(ins))
    return pl.pallas_call(
        functools.partial(_fused_momentum_kernel, lr=lr, beta=beta, wd=wd,
                          nesterov=nesterov, use_delta=use_delta,
                          use_bias=use_bias),
        grid=(w, r // block),
        in_specs=specs,
        out_specs=[specs[0], specs[0]],
        out_shape=[jax.ShapeDtypeStruct((w, r, c), p.dtype),
                   jax.ShapeDtypeStruct((w, r, c), m.dtype)],
        input_output_aliases={0: 0, len(ins) - 1: 1},
        name="vrl_local_momentum",
        interpret=interpret,
    )(*ins)


def _fused_adam_kernel(*refs, lr, b1, b2, eps, wd, use_delta, use_bias):
    v, i = _correction(refs, 2, use_delta, use_bias)
    mu_ref, nu_ref, s_ref = refs[i], refs[i + 1], refs[i + 2]
    po, muo, nuo = refs[-3], refs[-2], refs[-1]
    p = _f32(refs[0])
    c1 = s_ref[0, 0]    # 1 − b1^t  (dynamic: depends on the step count)
    c2 = s_ref[0, 1]    # 1 − b2^t
    mu = b1 * _f32(mu_ref) + (1.0 - b1) * v
    nu = b2 * _f32(nu_ref) + (1.0 - b2) * v * v
    step = lr * (mu / c1) / (jnp.sqrt(nu / c2) + eps)
    if wd:
        step = step + lr * wd * p
    po[...] = (p - step).astype(po.dtype)
    muo[...] = mu.astype(muo.dtype)
    nuo[...] = nu.astype(nuo.dtype)


def fused_local_adam(p, g, d, mu, nu, scal, *, lr: float, b1: float = 0.9,
                     b2: float = 0.999, eps: float = 1e-8, wd: float = 0.0,
                     block: int = 1024, interpret=None, b=None):
    """Adam inner step fused with the corrections.

    ``scal``: (1, 2) fp32 = [1 − b1^t, 1 − b2^t] (bias-correction terms are
    traced values, so they enter as data, not as static compile-time args).
    Returns (p', mu', nu').
    """
    if interpret is None:
        interpret = default_interpret()
    w, r, c = p.shape
    use_delta, use_bias = d is not None, b is not None
    ins = ((p, g) + ((d,) if use_delta else ())
           + ((b,) if use_bias else ()) + (mu, nu))
    block = _row_block(block, *ins, p, mu, nu)
    specs = _grid_specs(w, r, c, block, len(ins)) + [_scal_spec(2)]
    return pl.pallas_call(
        functools.partial(_fused_adam_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                          wd=wd, use_delta=use_delta, use_bias=use_bias),
        grid=(w, r // block),
        in_specs=specs,
        out_specs=[specs[0], specs[0], specs[0]],
        out_shape=[jax.ShapeDtypeStruct((w, r, c), p.dtype),
                   jax.ShapeDtypeStruct((w, r, c), mu.dtype),
                   jax.ShapeDtypeStruct((w, r, c), nu.dtype)],
        input_output_aliases={0: 0, len(ins) - 2: 1, len(ins) - 1: 2},
        name="vrl_local_adam",
        interpret=interpret,
    )(*ins, scal)


def _fused_adam_sm3_kernel(*refs, lr, b1, b2, eps, wd, tps, use_delta,
                           use_bias):
    """SM3-factored Adam: nu is never materialized at (W, R, C) — it is
    rebuilt per tile from the row stat (W, R, 1) and the per-shard lane
    stat (W, S, C) via v̂ = min(row, col), updated, and re-factored.

    The lane stat's output block is revisited by the ``tps`` consecutive
    row tiles of its shard (grid is row-major), so it is NOT donated —
    aliasing it would feed tile i+1 the partially-accumulated stat through
    the min() above.  First visit initializes, later visits max-accumulate;
    fp32 max is exact and order-free, so the result is bitwise the xla
    twin's single max over the shard's rows.
    """
    v, i = _correction(refs, 2, use_delta, use_bias)
    mu_ref, row_ref, col_ref, s_ref = refs[i], refs[i + 1], refs[i + 2], \
        refs[i + 3]
    po, muo, rowo, colo = refs[-4], refs[-3], refs[-2], refs[-1]
    p = _f32(refs[0])
    c1 = s_ref[0, 0]
    c2 = s_ref[0, 1]
    mu = b1 * _f32(mu_ref) + (1.0 - b1) * v
    vhat = jnp.minimum(_f32(row_ref), _f32(col_ref))
    nu = b2 * vhat + (1.0 - b2) * v * v
    step = lr * (mu / c1) / (jnp.sqrt(nu / c2) + eps)
    if wd:
        step = step + lr * wd * p
    po[...] = (p - step).astype(po.dtype)
    muo[...] = mu.astype(muo.dtype)
    rowo[...] = jnp.max(nu, axis=-1, keepdims=True).astype(rowo.dtype)
    tile_col = jnp.max(nu, axis=-2, keepdims=True).astype(colo.dtype)
    ti = pl.program_id(len(colo.shape) - 2)   # row-tile grid index
    first = (ti % tps) == 0

    @pl.when(first)
    def _init():
        colo[...] = tile_col

    @pl.when(jnp.logical_not(first))
    def _acc():
        colo[...] = jnp.maximum(colo[...], tile_col)


def fused_local_adam_sm3(p, g, d, mu, row, col, scal, *, lr: float,
                         b1: float = 0.9, b2: float = 0.999,
                         eps: float = 1e-8, wd: float = 0.0,
                         block: int = 1024, interpret=None, b=None):
    """SM3-factored Adam inner step fused with the corrections.

    ``row``: (W, R, 1) fp32 row-max stat; ``col``: (W, S, C) fp32 lane-max
    stat, one row per model shard's row span (S=1 ⇒ classic SM3 over the
    whole buffer).  Per-shard spans keep the stat update local under
    row-block sharding — a finer cover is still a valid upper bound.
    Returns (p', mu', row', col'); p/mu/row donated, col not (see kernel).
    """
    if interpret is None:
        interpret = default_interpret()
    w, r, c = p.shape
    shards = col.shape[-2]
    assert (r // block) % shards == 0, (r, block, shards)
    use_delta, use_bias = d is not None, b is not None
    ins = ((p, g) + ((d,) if use_delta else ())
           + ((b,) if use_bias else ()) + (mu, row))
    block = _row_block(block, *ins, p, mu, row)
    tps = (r // block) // shards
    # the lane stat rides as (W, S, 1, C) so its block's last two dims are
    # the array's own; the squeezed shard dim gives the kernel (1, 1, C)
    ins = ins + (col.reshape(w, shards, 1, c),)
    n3 = len(ins) - 2                   # (W, R, C) operands
    specs = _grid_specs(w, r, c, block, n3)
    row_spec = pl.BlockSpec((1, block, 1), lambda wi, i: (wi, i, 0))
    col_spec = pl.BlockSpec((1, pl.squeezed, 1, c),
                            lambda wi, i: (wi, i // tps, 0, 0))
    new_p, new_mu, new_row, new_col = pl.pallas_call(
        functools.partial(_fused_adam_sm3_kernel, lr=lr, b1=b1, b2=b2,
                          eps=eps, wd=wd, tps=tps, use_delta=use_delta,
                          use_bias=use_bias),
        grid=(w, r // block),
        in_specs=specs + [row_spec, col_spec, _scal_spec(2)],
        out_specs=[specs[0], specs[0], row_spec, col_spec],
        out_shape=[jax.ShapeDtypeStruct((w, r, c), p.dtype),
                   jax.ShapeDtypeStruct((w, r, c), mu.dtype),
                   jax.ShapeDtypeStruct(row.shape, jnp.float32),
                   jax.ShapeDtypeStruct(ins[-1].shape, jnp.float32)],
        input_output_aliases={0: 0, len(ins) - 3: 1, len(ins) - 2: 2},
        name="vrl_local_adam_sm3",
        interpret=interpret,
    )(*ins, scal)
    return new_p, new_mu, new_row, new_col.reshape(col.shape)


def _fused_sync_kernel(p_ref, xb_ref, d_ref, s_ref, po_ref, do_ref):
    p = _f32(p_ref)
    xb = _f32(xb_ref)[None]     # (block, C) broadcast over the worker dim
    kg = s_ref[0, 0]            # k_eff · γ  (k_eff is traced)
    do_ref[...] = (_f32(d_ref) + (xb - p) / kg).astype(do_ref.dtype)
    po_ref[...] = jnp.broadcast_to(xb, po_ref.shape).astype(po_ref.dtype)


def fused_sync_vrl(p, xbar, d, scal, *, block: int = 1024, interpret=None):
    """Δ' = Δ + (x̂ − p)/(k_eff γ); p' = x̂ — one pass, (W, R, C) buffers.

    ``xbar``: (R, C) — each worker's grid step reads the same x̂ tile, so the
    broadcast never materializes W copies in HBM.  ``scal``: (1, 1) fp32
    holding k_eff·γ (division matches the reference path's rounding exactly).
    Returns (p', Δ').
    """
    if interpret is None:
        interpret = default_interpret()
    w, r, c = p.shape
    block = _row_block(block, p, xbar, d, p, d)
    s3 = _grid_specs(w, r, c, block, 2)
    xb_spec = pl.BlockSpec((block, c), lambda wi, i: (i, 0))
    return pl.pallas_call(
        _fused_sync_kernel,
        grid=(w, r // block),
        in_specs=[s3[0], xb_spec, s3[1], _scal_spec(1)],
        out_specs=[s3[0], s3[0]],
        out_shape=[jax.ShapeDtypeStruct((w, r, c), p.dtype),
                   jax.ShapeDtypeStruct((w, r, c), d.dtype)],
        input_output_aliases={0: 0, 2: 1},
        name="vrl_sync",
        interpret=interpret,
    )(p, xbar, d, scal)


def _fused_sync_bvr_kernel(p_ref, xb_ref, d_ref, b_ref, s_ref, po_ref,
                           do_ref, bo_ref, *, beta: float):
    p = _f32(p_ref)
    xb = _f32(xb_ref)[None]     # (block, C) broadcast over the worker dim
    kg = s_ref[0, 0]            # k_eff · γ  (k_eff is traced)
    u = (xb - p) / kg           # realized drift this round
    do_ref[...] = (_f32(d_ref) + u).astype(do_ref.dtype)
    bo_ref[...] = ((1.0 - beta) * _f32(b_ref) + beta * u
                   ).astype(bo_ref.dtype)
    po_ref[...] = jnp.broadcast_to(xb, po_ref.shape).astype(po_ref.dtype)


def fused_sync_bvr(p, xbar, d, b, scal, *, beta: float, block: int = 1024,
                   interpret=None):
    """BVR-L-SGD sync: the VRL Δ update plus the bias-variate EMA, one pass.

      u  = (x̂ − p)/(k_eff γ)        Δ' = Δ + u
      B' = (1−β)·B + β·u            p' = x̂

    Same operand contract as ``fused_sync_vrl`` with the extra (W, R, C)
    bias buffer ``b``; β is static config.  Returns (p', Δ', B') with all
    three state buffers donated.
    """
    if interpret is None:
        interpret = default_interpret()
    w, r, c = p.shape
    block = _row_block(block, p, xbar, d, b, p, d, b)
    s3 = _grid_specs(w, r, c, block, 3)
    xb_spec = pl.BlockSpec((block, c), lambda wi, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_fused_sync_bvr_kernel, beta=beta),
        grid=(w, r // block),
        in_specs=[s3[0], xb_spec, s3[1], s3[2], _scal_spec(1)],
        out_specs=[s3[0], s3[0], s3[0]],
        out_shape=[jax.ShapeDtypeStruct((w, r, c), p.dtype),
                   jax.ShapeDtypeStruct((w, r, c), d.dtype),
                   jax.ShapeDtypeStruct((w, r, c), b.dtype)],
        input_output_aliases={0: 0, 2: 1, 3: 2},
        name="vrl_sync_bvr",
        interpret=interpret,
    )(p, xbar, d, b, scal)


# ====================================================== overlapped-round fold
# The overlapped round issues its sync all-reduce at round START over the
# positions every worker TRANSMITTED at the previous boundary (the ``pend``
# buffer of ``core.types.OverlapState``), so the collective runs concurrently
# with the round's local steps.  These kernels apply the one-round-stale
# result at round END, in one HBM pass:
#
#   c_i = x̂_stale − pend_i        the stale correction toward the mean
#   p'  = p + c_i                  fold into the live (scanned) params
#   Δ'  = Δ + c_i / (pend_k_i γ)   eq. 4 over the period pend covers
#   pend'_i = km_i·pend_i + (1−km_i)·p'     capture for the NEXT collective
#
# Σ_i c_i = 0, so the worker-mean trajectory is untouched and ΣΔ stays 0.
# ``wscal`` is a per-worker (W, 2) fp32 operand: column 0 = 1/(pend_k_i·γ)
# (pend_k differs per worker once deadlines are missed), column 1 = km_i,
# the miss mask (1 ⇒ the worker missed the capture deadline and keeps its
# last transmitted position; its shortfall transmits whole next time).
# ``capture=False`` drops the pend' output — the compressed-sync path
# captures outside the kernel via the EF round-trip instead.

def _wscal_spec(n: int):
    """Per-worker (1, n) row of a (W, n) operand carried as (W, 1, n): the
    block's last two dims are the array's own, as the TPU tiling rule asks,
    and the squeezed worker dim hands the kernel a (1, n) ref."""
    return pl.BlockSpec((pl.squeezed, 1, n), lambda wi, i: (wi, 0, 0))


def _fold_overlap_kernel(*refs, use_delta: bool, use_bias: bool,
                         beta: float, capture: bool):
    p_ref, xb_ref, pend_ref = refs[0], refs[1], refs[2]
    i = 3
    d_ref = b_ref = None
    if use_delta:
        d_ref = refs[i]
        i += 1
    if use_bias:
        b_ref = refs[i]
        i += 1
    s_ref = refs[i]
    outs = list(refs[i + 1:])
    pend = _f32(pend_ref)
    c = _f32(xb_ref)[None] - pend    # stale correction x̂_stale − pend_i
    pnew = _f32(p_ref) + c
    po_ref = outs.pop(0)
    po_ref[...] = pnew.astype(po_ref.dtype)
    if use_delta:
        inv = s_ref[0, 0]            # 1/(pend_k_i · γ)
        do_ref = outs.pop(0)
        do_ref[...] = (_f32(d_ref) + c * inv).astype(do_ref.dtype)
    if use_bias:
        inv = s_ref[0, 0]
        bo_ref = outs.pop(0)
        bo_ref[...] = ((1.0 - beta) * _f32(b_ref) + beta * c * inv
                       ).astype(bo_ref.dtype)
    if capture:
        km = s_ref[0, 1]             # 1 ⇒ missed deadline: keep old pend
        pendo_ref = outs.pop(0)
        pendo_ref[...] = (km * pend + (1.0 - km) * pnew
                          ).astype(pendo_ref.dtype)


def _fold_call(p, xbar, pend, d, b, wscal, *, beta, capture, block,
               interpret):
    """Shared pallas_call builder for the flat overlapped-round folds."""
    if interpret is None:
        interpret = default_interpret()
    w, r, c = p.shape
    use_delta, use_bias = d is not None, b is not None
    ins = ((p, xbar, pend) + ((d,) if use_delta else ())
           + ((b,) if use_bias else ()))
    n3 = len(ins) - 1               # (W, R, C) operands (all but xbar)
    n_out = 1 + use_delta + use_bias + capture
    out_shape = [jax.ShapeDtypeStruct((w, r, c), p.dtype)]
    if use_delta:
        out_shape.append(jax.ShapeDtypeStruct((w, r, c), d.dtype))
    if use_bias:
        out_shape.append(jax.ShapeDtypeStruct((w, r, c), b.dtype))
    if capture:
        out_shape.append(jax.ShapeDtypeStruct((w, r, c), pend.dtype))
    block = _row_block(block, *ins, *out_shape)
    s3 = _grid_specs(w, r, c, block, n3)
    xb_spec = pl.BlockSpec((block, c), lambda wi, i: (i, 0))
    in_specs = [s3[0], xb_spec] + s3[1:] + [_wscal_spec(2)]
    # donate every state buffer onto its output: p→p', Δ→Δ', B→B',
    # pend→pend' (operand index: xbar sits at 1, pend at 2)
    aliases = {0: 0}
    oi = 1
    if use_delta:
        aliases[3] = oi
        oi += 1
    if use_bias:
        aliases[3 + use_delta] = oi
        oi += 1
    if capture:
        aliases[2] = oi
    return pl.pallas_call(
        functools.partial(_fold_overlap_kernel, use_delta=use_delta,
                          use_bias=use_bias, beta=beta, capture=capture),
        grid=(w, r // block),
        in_specs=in_specs,
        out_specs=[s3[0]] * n_out,
        out_shape=out_shape,
        input_output_aliases=aliases,
        name="vrl_fold_overlap",
        interpret=interpret,
    )(*ins, wscal.reshape(w, 1, 2))


def fused_fold_overlap(p, xbar, pend, d, wscal, *, capture: bool = True,
                       block: int = 1024, interpret=None):
    """Stale-sync fold for the VRL algorithms, one pass over (W, R, C).

      c = x̂_stale − pend;  p' = p + c;  Δ' = Δ + c/(pend_k γ);
      pend' = km·pend + (1−km)·p'

    ``xbar``: (R, C) — the round-start all-reduce over pend (stale by one
    round).  ``wscal``: (W, 2) fp32 [1/(pend_k_i·γ), km_i] per worker.
    Returns (p', Δ', pend'), all donated; ``capture=False`` returns
    (p', Δ') and leaves the capture to the caller (compressed sync).
    """
    return _fold_call(p, xbar, pend, d, None, wscal, beta=0.0,
                      capture=capture, block=block, interpret=interpret)


def fused_fold_overlap_bvr(p, xbar, pend, d, b, wscal, *, beta: float,
                           capture: bool = True, block: int = 1024,
                           interpret=None):
    """BVR-L-SGD stale fold: the VRL fold plus the bias-variate EMA
    B' = (1−β)B + β·c/(pend_k γ).  Returns (p', Δ', B'[, pend'])."""
    return _fold_call(p, xbar, pend, d, b, wscal, beta=beta,
                      capture=capture, block=block, interpret=interpret)


def fused_fold_overlap_avg(p, xbar, pend, wscal, *, capture: bool = True,
                           block: int = 1024, interpret=None):
    """Average-sync stale fold (local_sgd / stl_sgd): p' = p + c only —
    no Δ.  Returns (p'[, pend'])."""
    return _fold_call(p, xbar, pend, None, None, wscal, beta=0.0,
                      capture=capture, block=block, interpret=interpret)


def _fold_overlap_hier2_kernel(*refs, capture: bool):
    p_ref, g_ref, pend_ref, d2_ref, s_ref = refs[:5]
    po_ref, do_ref = refs[5], refs[6]
    pend = _f32(pend_ref)
    c = _f32(g_ref)[None] - pend     # stale cross-pod correction per pod
    pnew = _f32(p_ref) + c
    po_ref[...] = pnew.astype(po_ref.dtype)
    inv = s_ref[0, 0]                # 1/(pend_k2_p · γ)
    do_ref[...] = (_f32(d2_ref) + c * inv).astype(do_ref.dtype)
    if capture:
        km = s_ref[0, 1]
        pendo_ref = refs[7]
        pendo_ref[...] = (km * pend + (1.0 - km) * pnew
                          ).astype(pendo_ref.dtype)


def fused_fold_overlap_hier2(p, glob, pend2, d2, wscal, *,
                             capture: bool = True, block: int = 1024,
                             interpret=None):
    """Level-2 stale fold: c = x̂_stale − pend2_p folded into every worker
    of pod p, Δ2' = Δ2 + c/(pend_k2 γ), pend2' captured per pod.

    Assumes a level-1 sync at the same step (like ``fused_sync_hier2``),
    so every worker's folded params equal its pod average and the per-pod
    outputs are well-defined.  ``glob``: (R, C) stale cross-pod mean;
    ``wscal``: (P, 2).  The intra-pod grid dim is innermost; the D
    revisits of each Δ2'/pend2' block write identical values, so those
    buffers are NOT donated (aliasing would feed revisit di+1 the
    already-updated block).  Returns (p', Δ2'[, pend2']) with p donated.
    """
    if interpret is None:
        interpret = default_interpret()
    pp, dd, r, c = p.shape
    block = _row_block(block, p, glob, pend2, d2, p, d2,
                       *((pend2,) if capture else ()))
    wspec = pl.BlockSpec((1, 1, block, c), lambda pi, i, di: (pi, di, i, 0))
    podspec = pl.BlockSpec((1, 1, block, c), lambda pi, i, di: (pi, 0, i, 0))
    gspec = pl.BlockSpec((block, c), lambda pi, i, di: (i, 0))
    # (P, 2) per-pod scalars ride as (P, 1, 2): see _wscal_spec
    sspec = pl.BlockSpec((pl.squeezed, 1, 2), lambda pi, i, di: (pi, 0, 0))
    out_specs = [wspec, podspec] + ([podspec] if capture else [])
    out_shape = [jax.ShapeDtypeStruct(p.shape, p.dtype),
                 jax.ShapeDtypeStruct(d2.shape, d2.dtype)] \
        + ([jax.ShapeDtypeStruct(pend2.shape, pend2.dtype)]
           if capture else [])
    return pl.pallas_call(
        functools.partial(_fold_overlap_hier2_kernel, capture=capture),
        grid=(pp, r // block, dd),
        in_specs=[wspec, gspec, podspec, podspec, sspec],
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={0: 0},
        name="vrl_fold_overlap_hier2",
        interpret=interpret,
    )(p, glob, pend2, d2, wscal.reshape(pp, 1, 2))


def _easgd_worker_kernel(p_ref, c_ref, po_ref, *, a: float):
    p = _f32(p_ref)
    c = _f32(c_ref)[None]       # (block, C) broadcast over the worker dim
    po_ref[...] = (p - a * (p - c)).astype(po_ref.dtype)


def _easgd_center_kernel(c_ref, xb_ref, co_ref, *, na: float):
    co_ref[...] = ((1.0 - na) * _f32(c_ref)
                   + na * _f32(xb_ref)).astype(co_ref.dtype)


def fused_sync_easgd(p, xbar, center, *, a: float, na: float,
                     block: int = 1024, interpret=None):
    """Elastic sync (Zhang et al.) fused on flat buffers; returns (p', c').

      p' = p − a·(p − x̃)            a  = easgd_alpha / N
      c' = (1 − N·a)·x̃ + N·a·x̂     na = N·a

    ``p``: (W, R, C); ``xbar``/``center``: (R, C) fp32 (x̂ is the worker
    mean — THE all-reduce — computed by the caller before this pass).  Two
    single-pass kernels so both p and x̃ can be donated; the p' pass reads
    the OLD center, so XLA's alias analysis orders it before (or copies
    around) the in-place center update.
    """
    if interpret is None:
        interpret = default_interpret()
    w, r, c = p.shape
    block = _row_block(block, p, center, p)
    pspec = _grid_specs(w, r, c, block, 1)[0]
    cspec = pl.BlockSpec((block, c), lambda wi, i: (i, 0))
    new_p = pl.pallas_call(
        functools.partial(_easgd_worker_kernel, a=a),
        grid=(w, r // block),
        in_specs=[pspec, cspec],
        out_specs=pspec,
        out_shape=jax.ShapeDtypeStruct((w, r, c), p.dtype),
        input_output_aliases={0: 0},
        name="easgd_sync_worker",
        interpret=interpret,
    )(p, center)
    flat2 = pl.BlockSpec((block, c), lambda i: (i, 0))
    new_c = pl.pallas_call(
        functools.partial(_easgd_center_kernel, na=na),
        grid=(r // block,),
        in_specs=[flat2, flat2],
        out_specs=flat2,
        out_shape=jax.ShapeDtypeStruct((r, c), center.dtype),
        input_output_aliases={0: 0},
        name="easgd_sync_center",
        interpret=interpret,
    )(center, xbar)
    return new_p, new_c


# ==================================================== compressed-sync kernels
# EF round-trips of the sync payload's drift (repro.comm): one HBM pass
# builds payload = p − ref + resid, quantizes / sparsifies it, and emits the
# decompressed payload (what the single flat all-reduce then carries) plus
# the new error-feedback residual, with the residual donated in place.  Row
# statistics (the int8 per-row scale, the top-k per-row threshold) stay
# entirely inside one (block, C) tile because tiles split rows, never lanes.
#
# The math mirrors ``repro.comm.compressors.ef_int8`` / ``ef_topk`` exactly
# (same formulas, fp32 in-register) so the three executors agree; the wire
# REPRESENTATION (int8 values + scales / fixed-k values + indices) is built
# by ``repro.comm.compressors.compress`` for byte measurement — the engine hot
# path only ever needs the decompressed payload and the residual.
#
# Top-k selection finds each row's kth magnitude by a bisection over the
# int32 bit pattern of |x| (``compressors.kth_magnitude_bits``), because
# ``lax.top_k`` has no Mosaic lowering.

def _ef_kernel(*refs, mode: str, k: int, use_ref: bool, use_ef: bool):
    # the round-trip math is the CANONICAL repro.comm implementation —
    # its jnp ops trace inside the kernel body, so the executors cannot
    # drift apart formula-wise
    x = _f32(refs[0])
    i = 1
    if use_ref:
        x = x - _f32(refs[i])
        i += 1
    if use_ef:
        x = x + _f32(refs[i])
        i += 1
    dec, resid = (cc.ef_int8(x) if mode == "int8" else cc.ef_topk(x, k))
    dec_ref = refs[i]
    dec_ref[...] = dec.astype(dec_ref.dtype)
    if use_ef:
        eo_ref = refs[i + 1]
        eo_ref[...] = resid.astype(eo_ref.dtype)


def _ef_call(p, ref, e, *, mode: str, k: int, block: int, interpret,
             grid_kind: str):
    """Shared pallas_call builder for the flat (W, R, C) and pod-major
    (P, D, R, C) EF round-trips.  Returns (dec fp32, resid' | None); the
    residual aliases its input buffer (donated in place)."""
    if interpret is None:
        interpret = default_interpret()
    use_ref, use_ef = ref is not None, e is not None
    c = p.shape[-1]
    block = _row_block(block, p, *((ref,) if use_ref else ()),
                       *((e, e) if use_ef else ()),
                       jax.ShapeDtypeStruct(p.shape, jnp.float32))
    if grid_kind == "flat":
        w, r, _ = p.shape
        grid = (w, r // block)
        wspec = pl.BlockSpec((1, block, c), lambda wi, i: (wi, i, 0))
        # shared (R, C) reference: every worker's step reads the same tile
        rspec = pl.BlockSpec((block, c), lambda wi, i: (i, 0))
    else:
        pp, dd, r, _ = p.shape
        grid = (pp, dd, r // block)
        wspec = pl.BlockSpec((1, 1, block, c),
                             lambda pi, di, i: (pi, di, i, 0))
        # per-pod (P, 1, R, C) reference: broadcast over the intra-pod dim
        rspec = pl.BlockSpec((1, 1, block, c),
                             lambda pi, di, i: (pi, 0, i, 0))
    ins = (p,) + ((ref,) if use_ref else ()) + ((e,) if use_ef else ())
    in_specs = [wspec] + ([rspec] if use_ref else []) \
        + ([wspec] if use_ef else [])
    out_specs = [wspec] + ([wspec] if use_ef else [])
    out_shape = [jax.ShapeDtypeStruct(p.shape, jnp.float32)] \
        + ([jax.ShapeDtypeStruct(e.shape, e.dtype)] if use_ef else [])
    aliases = {len(ins) - 1: 1} if use_ef else {}
    out = pl.pallas_call(
        functools.partial(_ef_kernel, mode=mode, k=k, use_ref=use_ref,
                          use_ef=use_ef),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        name=f"vrl_ef_{mode}",
        interpret=interpret,
    )(*ins)
    if use_ef:
        return out[0], out[1]
    return out[0], None


def fused_ef_int8(p, ref, e, *, block: int = 1024, interpret=None):
    """Per-row-scaled int8 EF round-trip on (W, R, C) buffers.

    ``ref``: (R, C) shared drift reference or None (S-SGD gradient
    compression); ``e``: (W, R, C) error-feedback residual or None.
    Returns (decompressed payload fp32, resid'), resid' donated in place
    and None when ``e`` is None.
    """
    return _ef_call(p, ref, e, mode="int8", k=0, block=block,
                    interpret=interpret, grid_kind="flat")


def fused_ef_topk(p, ref, e, *, k: int, block: int = 1024, interpret=None):
    """Top-k (k lanes kept per row) EF round-trip on (W, R, C) buffers;
    same operand contract as ``fused_ef_int8``."""
    return _ef_call(p, ref, e, mode="topk", k=k, block=block,
                    interpret=interpret, grid_kind="flat")


def fused_ef_int8_grid(p, ref, e, *, block: int = 1024, interpret=None):
    """Pod-major twin: p/e (P, D, R, C), ref (P, 1, R, C) per-pod
    reference whose blocks broadcast over the intra-pod grid dim."""
    return _ef_call(p, ref, e, mode="int8", k=0, block=block,
                    interpret=interpret, grid_kind="grid")


def fused_ef_topk_grid(p, ref, e, *, k: int, block: int = 1024,
                       interpret=None):
    return _ef_call(p, ref, e, mode="topk", k=k, block=block,
                    interpret=interpret, grid_kind="grid")


# ================================================== hierarchical (P, D, R, C)
# Pod-major worker-grid kernels for the two-level engine.  Grid =
# (P, D, R/block); per-worker buffers stream as (1, 1, block, C) tiles while
# the per-pod Δ2 / pod-average tiles are broadcast over the intra-pod grid
# dim by their index map (one HBM read, no (P, D)-sized materialization).

def _grid4_specs(block: int, c: int, n: int):
    return [pl.BlockSpec((1, 1, block, c), lambda pi, di, i: (pi, di, i, 0))
            for _ in range(n)]


def _pod4_spec(block: int, c: int):
    """(P, 1, R, C) operand: every worker in pod pi reads block (pi, 0, i)."""
    return pl.BlockSpec((1, 1, block, c), lambda pi, di, i: (pi, 0, i, 0))


def _scal4_spec(n: int):
    return pl.BlockSpec((1, n), lambda pi, di, i: (0, 0))


def _hier_sgd_kernel(p_ref, g_ref, d1_ref, d2_ref, o_ref, *, lr, wd):
    v = _f32(g_ref) - _f32(d1_ref) - _f32(d2_ref)
    p = _f32(p_ref)
    if wd:
        v = v + wd * p
    o_ref[...] = (p - lr * v).astype(o_ref.dtype)


def fused_hier_local_sgd(p, g, d1, d2, *, lr: float, wd: float = 0.0,
                         block: int = 1024, interpret=None):
    """p' = p − γ((g − Δ1 − Δ2) + wd·p) on (P, D, R, C) buffers."""
    if interpret is None:
        interpret = default_interpret()
    pp, dd, r, c = p.shape
    block = _row_block(block, p, g, d1, d2, p)
    specs = _grid4_specs(block, c, 3)
    return pl.pallas_call(
        functools.partial(_hier_sgd_kernel, lr=lr, wd=wd),
        grid=(pp, dd, r // block),
        in_specs=[specs[0], specs[1], specs[2], _pod4_spec(block, c)],
        out_specs=specs[0],
        out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
        input_output_aliases={0: 0},
        name="vrl_hier_local_sgd",
        interpret=interpret,
    )(p, g, d1, d2)


def _hier_momentum_kernel(p_ref, g_ref, d1_ref, d2_ref, m_ref, po_ref,
                          mo_ref, *, lr, beta, wd, nesterov):
    v = _f32(g_ref) - _f32(d1_ref) - _f32(d2_ref)
    p = _f32(p_ref)
    if wd:
        v = v + wd * p
    m_new = beta * _f32(m_ref) + v
    step_dir = v + beta * m_new if nesterov else m_new
    po_ref[...] = (p - lr * step_dir).astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)


def fused_hier_local_momentum(p, g, d1, d2, m, *, lr: float, beta: float,
                              wd: float = 0.0, nesterov: bool = False,
                              block: int = 1024, interpret=None):
    """Momentum inner step with both Δ corrections; returns (p', m')."""
    if interpret is None:
        interpret = default_interpret()
    pp, dd, r, c = p.shape
    block = _row_block(block, p, g, d1, d2, m, p, m)
    specs = _grid4_specs(block, c, 4)
    return pl.pallas_call(
        functools.partial(_hier_momentum_kernel, lr=lr, beta=beta, wd=wd,
                          nesterov=nesterov),
        grid=(pp, dd, r // block),
        in_specs=[specs[0], specs[1], specs[2], _pod4_spec(block, c),
                  specs[3]],
        out_specs=[specs[0], specs[3]],
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct(m.shape, m.dtype)],
        input_output_aliases={0: 0, 4: 1},
        name="vrl_hier_local_momentum",
        interpret=interpret,
    )(p, g, d1, d2, m)


def _hier_adam_kernel(p_ref, g_ref, d1_ref, d2_ref, mu_ref, nu_ref, s_ref,
                      po, muo, nuo, *, lr, b1, b2, eps, wd):
    v = _f32(g_ref) - _f32(d1_ref) - _f32(d2_ref)
    p = _f32(p_ref)
    c1 = s_ref[0, 0]
    c2 = s_ref[0, 1]
    mu = b1 * _f32(mu_ref) + (1.0 - b1) * v
    nu = b2 * _f32(nu_ref) + (1.0 - b2) * v * v
    step = lr * (mu / c1) / (jnp.sqrt(nu / c2) + eps)
    if wd:
        step = step + lr * wd * p
    po[...] = (p - step).astype(po.dtype)
    muo[...] = mu.astype(muo.dtype)
    nuo[...] = nu.astype(nuo.dtype)


def fused_hier_local_adam(p, g, d1, d2, mu, nu, scal, *, lr: float,
                          b1: float = 0.9, b2: float = 0.999,
                          eps: float = 1e-8, wd: float = 0.0,
                          block: int = 1024, interpret=None):
    """Adam inner step with both Δ corrections; returns (p', mu', nu')."""
    if interpret is None:
        interpret = default_interpret()
    pp, dd, r, c = p.shape
    block = _row_block(block, p, g, d1, d2, mu, nu, p, mu, nu)
    specs = _grid4_specs(block, c, 5)
    return pl.pallas_call(
        functools.partial(_hier_adam_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                          wd=wd),
        grid=(pp, dd, r // block),
        in_specs=[specs[0], specs[1], specs[2], _pod4_spec(block, c),
                  specs[3], specs[4], _scal4_spec(2)],
        out_specs=[specs[0], specs[3], specs[4]],
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct(mu.shape, mu.dtype),
                   jax.ShapeDtypeStruct(nu.shape, nu.dtype)],
        input_output_aliases={0: 0, 4: 1, 5: 2},
        name="vrl_hier_local_adam",
        interpret=interpret,
    )(p, g, d1, d2, mu, nu, scal)


def _hier_adam_sm3_kernel(p_ref, g_ref, d1_ref, d2_ref, mu_ref, row_ref,
                          col_ref, s_ref, po, muo, rowo, colo, *, lr, b1,
                          b2, eps, wd, tps):
    """Pod-major SM3 Adam — same factored construction as
    ``_fused_adam_sm3_kernel`` with v = g − Δ1 − Δ2; the innermost grid
    dim is the row tile, so the lane stat's ``tps`` revisits stay
    consecutive (col NOT donated, same aliasing hazard)."""
    v = _f32(g_ref) - _f32(d1_ref) - _f32(d2_ref)
    p = _f32(p_ref)
    c1 = s_ref[0, 0]
    c2 = s_ref[0, 1]
    mu = b1 * _f32(mu_ref) + (1.0 - b1) * v
    vhat = jnp.minimum(_f32(row_ref), _f32(col_ref))
    nu = b2 * vhat + (1.0 - b2) * v * v
    step = lr * (mu / c1) / (jnp.sqrt(nu / c2) + eps)
    if wd:
        step = step + lr * wd * p
    po[...] = (p - step).astype(po.dtype)
    muo[...] = mu.astype(muo.dtype)
    rowo[...] = jnp.max(nu, axis=-1, keepdims=True).astype(rowo.dtype)
    tile_col = jnp.max(nu, axis=-2, keepdims=True).astype(colo.dtype)
    first = (pl.program_id(2) % tps) == 0

    @pl.when(first)
    def _init():
        colo[...] = tile_col

    @pl.when(jnp.logical_not(first))
    def _acc():
        colo[...] = jnp.maximum(colo[...], tile_col)


def fused_hier_local_adam_sm3(p, g, d1, d2, mu, row, col, scal, *,
                              lr: float, b1: float = 0.9, b2: float = 0.999,
                              eps: float = 1e-8, wd: float = 0.0,
                              block: int = 1024, interpret=None):
    """SM3-factored Adam with both Δ corrections on (P, D, R, C) buffers.

    ``row``: (P, D, R, 1); ``col``: (P, D, S, C) per-shard lane stats.
    Returns (p', mu', row', col'); p/mu/row donated, col not.
    """
    if interpret is None:
        interpret = default_interpret()
    pp, dd, r, c = p.shape
    shards = col.shape[-2]
    assert (r // block) % shards == 0, (r, block, shards)
    block = _row_block(block, p, g, d1, d2, mu, row, p, mu, row)
    tps = (r // block) // shards
    specs = _grid4_specs(block, c, 4)
    row_spec = pl.BlockSpec((1, 1, block, 1),
                            lambda pi, di, i: (pi, di, i, 0))
    # (P, D, S, 1, C) lane stat: see fused_local_adam_sm3
    col4 = col.reshape(pp, dd, shards, 1, c)
    col_spec = pl.BlockSpec((1, 1, pl.squeezed, 1, c),
                            lambda pi, di, i: (pi, di, i // tps, 0, 0))
    new_p, new_mu, new_row, new_col = pl.pallas_call(
        functools.partial(_hier_adam_sm3_kernel, lr=lr, b1=b1, b2=b2,
                          eps=eps, wd=wd, tps=tps),
        grid=(pp, dd, r // block),
        in_specs=[specs[0], specs[1], specs[2], _pod4_spec(block, c),
                  specs[3], row_spec, col_spec, _scal4_spec(2)],
        out_specs=[specs[0], specs[3], row_spec, col_spec],
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct(mu.shape, mu.dtype),
                   jax.ShapeDtypeStruct(row.shape, jnp.float32),
                   jax.ShapeDtypeStruct(col4.shape, jnp.float32)],
        input_output_aliases={0: 0, 4: 1, 5: 2},
        name="vrl_hier_local_adam_sm3",
        interpret=interpret,
    )(p, g, d1, d2, mu, row, col4, scal)
    return new_p, new_mu, new_row, new_col.reshape(col.shape)


def _hier_sync1_kernel(p_ref, xb_ref, d_ref, s_ref, po_ref, do_ref):
    p = _f32(p_ref)
    xb = _f32(xb_ref)
    kg = s_ref[0, 0]            # k1_eff · γ  (k1_eff is traced)
    do_ref[...] = (_f32(d_ref) + (xb - p) / kg).astype(do_ref.dtype)
    po_ref[...] = xb.astype(po_ref.dtype)


def fused_sync_hier1(p, xbar_pod, d1, scal, *, block: int = 1024,
                     interpret=None):
    """Level-1 (intra-pod) sync: Δ1' = Δ1 + (x̂_pod − p)/(k1γ); p' = x̂_pod.

    ``xbar_pod``: (P, 1, R, C) — the pod average the caller produced with
    the single intra-pod all-reduce.  One pass over (P, D, R, C); p and Δ1
    are donated.  Returns (p', Δ1').
    """
    if interpret is None:
        interpret = default_interpret()
    pp, dd, r, c = p.shape
    block = _row_block(block, p, xbar_pod, d1, p, d1)
    specs = _grid4_specs(block, c, 2)
    return pl.pallas_call(
        _hier_sync1_kernel,
        grid=(pp, dd, r // block),
        in_specs=[specs[0], _pod4_spec(block, c), specs[1], _scal4_spec(1)],
        out_specs=[specs[0], specs[1]],
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct(d1.shape, d1.dtype)],
        input_output_aliases={0: 0, 2: 1},
        name="vrl_sync_hier1",
        interpret=interpret,
    )(p, xbar_pod, d1, scal)


def _hier_sync2_kernel(p_ref, g_ref, d2_ref, s_ref, po_ref, do_ref):
    pod = _f32(p_ref)           # own params == pod average (post level-1)
    glob = _f32(g_ref)[None]
    kg = s_ref[0, 0]            # k2_eff · γ
    do_ref[...] = (_f32(d2_ref) + (glob - pod) / kg).astype(do_ref.dtype)
    po_ref[...] = jnp.broadcast_to(glob, po_ref.shape).astype(po_ref.dtype)


def fused_sync_hier2(p, glob, d2, scal, *, block: int = 1024,
                     interpret=None):
    """Level-2 (cross-pod) sync: Δ2' = Δ2 + (x̂ − x̂_pod)/(k2γ); p' = x̂.

    Assumes a level-1 sync at the same step, so every worker's params ARE
    its pod average — each grid step reads its OWN (pi, di) block as x̂_pod
    (never a block another step may have overwritten in-place).  ``glob``:
    (R, C) — produced by the caller's single cross-pod all-reduce.  The
    intra-pod grid dim is innermost so the D revisits of each Δ2' block are
    consecutive; every revisit writes the same value (Δ2 itself is NOT
    donated — aliasing it would feed step di+1 the already-updated block).
    Returns (p', Δ2') with p donated.
    """
    if interpret is None:
        interpret = default_interpret()
    pp, dd, r, c = p.shape
    block = _row_block(block, p, glob, d2, p, d2)
    wspec = pl.BlockSpec((1, 1, block, c), lambda pi, i, di: (pi, di, i, 0))
    podspec = pl.BlockSpec((1, 1, block, c), lambda pi, i, di: (pi, 0, i, 0))
    gspec = pl.BlockSpec((block, c), lambda pi, i, di: (i, 0))
    return pl.pallas_call(
        _hier_sync2_kernel,
        grid=(pp, r // block, dd),
        in_specs=[wspec, gspec, podspec, _scal4_spec(1)],
        out_specs=[wspec, podspec],
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct(d2.shape, d2.dtype)],
        input_output_aliases={0: 0},
        name="vrl_sync_hier2",
        interpret=interpret,
    )(p, glob, d2, scal)
