"""Sync-payload compression — the bytes-per-round axis of communication
complexity.

The paper (and PRs 1-4) drive down the ROUNDS term of communication cost:
one flat all-reduce per sync, stagewise cadences, a hierarchical k2 period
for the slow cross-pod tier.  This module owns the orthogonal axis — how
many BYTES each of those rounds has to move.  Spiridonoff & Olshevsky
(2020) show the round count can be pushed to depend only on N, at which
point the per-round payload is the binding cost; a compressor composes
multiplicatively with every schedule and algorithm in the engine.

Compressors
-----------

``CompressorSpec`` names one of three wire formats over the engine's flat
(R, C) payload rows (layout: ``core/flat``):

  * ``none`` — identity.  Resolved to "no compressor at all": the engine
    takes its original code path, bitwise, with no extra state buffers.
  * ``int8`` — per-row-scaled linear quantization: each row of C lanes is
    scaled by max|row|/127 and rounded to int8.  Wire: 1 byte/element plus
    one fp32 scale per row.
  * ``topk`` — per-row magnitude sparsification with a FIXED k = C //
    ``rate`` survivors per row (fixed k ⇒ the wire layout is static and
    jittable: (rows, k) values + (rows, k) int32 indices, no variable-
    length segments).  ``rate=1`` keeps every lane and resolves to the
    identity path like ``none``.

What gets compressed: the DRIFT of each worker's payload against a shared
reference, not the payload itself.  Every sync already ends by installing a
value every participant knows (the broadcast mean x̂, or for EASGD the
shared mean it computed), so the engine carries that value as a ``ref``
buffer and each worker transmits ``compress(x_i − ref [+ residual])``.
Because ref is identical across the averaging group, the mean reconstructs
exactly: ``mean_i(x_i) = ref + mean_i(x_i − ref)``.  Drift compression is
what makes top-k sane (zeroing 1−1/rate of raw *parameters* would destroy
the model; zeroing small *drifts* just defers them) and shrinks int8's
quantization range.  S-SGD has no sync — its per-step gradient all-reduce
is the payload instead, compressed with ref ≡ 0 (classic QSGD/EF-SGD).

Error feedback: the compression error ``payload − decompress(compress(
payload))`` is carried per worker in a ``resid`` buffer and added to the
next round's payload before compressing (EF-SGD, Stich et al. 2018 — the
same carried-correction pattern as BVR-L-SGD's bias buffer).  The residual
is computed by literal subtraction, so the invariant

    residual' + decompressed == payload        (bitwise, in fp32)

holds by construction; it is property-tested in ``tests/test_compressors``.

Byte accounting
---------------

``wire_bytes`` is the measured one-way payload for one (R, C) buffer.  The
RAW baseline is what the engine's all-reduce actually carries today — the
full padded flat buffer (R·C·itemsize; the 2.00 GB/round figure on the
16×16 mesh comes from exactly this buffer in the compiled HLO).  The
compressed wire skips the tile-padding rows (padding is a Pallas-tiling
artifact; a byte-stream transport has no reason to send rows that are
identically zero by construction), transmitting ``used_rows =
ceil(size/lanes)`` rows.  ``compress``/``decompress`` build the actual
wire representation arrays so benchmarks measure real ``nbytes``, not a
formula.

Layering: this module is pure jnp + numpy — the canonical math.  The
engine's executors reuse it: ``kernels/xla_update`` wraps ``ef_int8`` /
``ef_topk`` directly, ``kernels/vrl_update`` re-states the same formulas
as Pallas kernel bodies (single HBM pass, residual donated), and the
per-leaf reference executor goes through ``ef_leaf``.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


COMPRESSORS = ("none", "int8", "topk")
_DEFAULT_TOPK_RATE = 32


class CompressorSpec(NamedTuple):
    """A named wire format for the sync payload.

    ``rate`` is the top-k keep divisor (k = lanes // rate survivors per
    row); it is 0 for the compressors that have no rate knob so specs
    compare/hash canonically.  ``error_feedback`` carries the compression
    error across rounds in a per-worker residual buffer.
    """

    name: str
    rate: int = 0
    error_feedback: bool = True

    def label(self) -> str:
        tag = f":{self.rate}" if self.name == "topk" else ""
        ef = "" if self.error_feedback or self.name == "none" else ":noef"
        return f"{self.name}{tag}{ef}"


def parse_compressor(text: str) -> CompressorSpec:
    """CLI syntax for ``--compress`` / ``--compress2``:

      "none"            identity (the uncompressed path, bitwise)
      "int8"            per-row-scaled int8 quantization
      "topk"            top-k sparsification, default rate 32 (C//32 kept)
      "topk:8"          explicit keep divisor (k = lanes // 8 per row)
      "int8:noef"       any compressor with error feedback disabled
    """
    parts = [p for p in text.split(":") if p]
    if not parts or parts[0] not in COMPRESSORS:
        raise ValueError(f"unknown compressor {text!r}; expected "
                         f"{'|'.join(COMPRESSORS)}[:rate][:noef]")
    name = parts[0]
    rate = _DEFAULT_TOPK_RATE if name == "topk" else 0
    ef = True
    for p in parts[1:]:
        if p == "noef":
            ef = False
        elif p == "ef":
            ef = True
        elif p.isdigit():
            if name != "topk":
                raise ValueError(f"{name!r} takes no rate (got {text!r})")
            rate = int(p)
        else:
            raise ValueError(f"bad compressor option {p!r} in {text!r}")
    return CompressorSpec(name=name, rate=rate, error_feedback=ef)


def as_spec(c) -> Optional[CompressorSpec]:
    if c is None or isinstance(c, CompressorSpec):
        return c
    if isinstance(c, str):
        return parse_compressor(c)
    raise TypeError(f"expected CompressorSpec | str | None, got {type(c)}")


def is_identity(c) -> bool:
    """True when the compressor changes nothing — the engine must then take
    its ORIGINAL code path (bitwise identical, no extra state buffers)."""
    c = as_spec(c)
    if c is None or c.name == "none":
        return True
    return c.name == "topk" and c.rate <= 1


def resolve(c) -> Optional[CompressorSpec]:
    """Spec for an active compressor, None for the identity path."""
    c = as_spec(c)
    return None if is_identity(c) else c


def resolve_pair(cfg) -> Tuple[Optional[CompressorSpec],
                               Optional[CompressorSpec]]:
    """(level-1, level-2) compressors for a VRLConfig.

    ``compress`` drives the (only) sync of the flat algorithms and the
    intra-pod level-1 sync of the hierarchical one; ``compress2`` overrides
    the cross-pod level-2 sync (so the slow DCI tier can compress harder)
    and falls back to ``compress`` when unset.
    """
    c1 = resolve(getattr(cfg, "compress", None))
    c2_raw = getattr(cfg, "compress2", None)
    c2 = resolve(c2_raw) if c2_raw is not None else c1
    return c1, c2


def topk_k(spec: CompressorSpec, lanes: int) -> int:
    """Survivors per row — fixed at trace time (the jittable layout)."""
    return max(1, lanes // max(spec.rate, 1))


def used_rows(size: int, lanes: int) -> int:
    """Rows carrying real elements — the wire skips pure tile padding."""
    return -(-size // lanes)


# ============================================================== EF round-trip
# The canonical compress→decompress math over (..., R, C) payload buffers,
# in fp32.  Returns (decompressed, residual); residual is the literal
# subtraction, so resid + dec == payload bitwise.

def ef_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-row-scaled int8 round-trip: scale = max|row|/127, symmetric
    round-to-nearest.  All-zero rows quantize to zero exactly."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = amax / 127.0
    safe = jnp.where(amax > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -127.0, 127.0)
    dec = q * scale
    return dec, x - dec


def ef_topk(x: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Keep the k largest-magnitude lanes per row, zero the rest.

    Selection is by threshold (the kth magnitude per row) so the Pallas
    kernel, the jnp twin, and this function agree bitwise.  Tie semantics:
    on EXACT magnitude ties at the threshold, threshold-keep retains every
    tied lane (>= k survivors), while the fixed-k wire format
    (``compress``) carries exactly k of them — so the wire reconstruction
    can differ from this round-trip at tied lanes (e.g. +x and −x tied at
    the kth magnitude).  Exact fp32 ties have measure zero for real
    payloads; the engine uses THIS round-trip, and the wire bytes it
    reports are exact-k (a lower bound on tied rows).
    """
    c = x.shape[-1]
    if k >= c:
        return x, jnp.zeros_like(x)
    bits = kth_magnitude_bits(x, k)
    keep = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32) >= bits
    dec = jnp.where(keep, x, jnp.zeros_like(x))
    return dec, x - dec


def kth_magnitude_bits(x: jax.Array, k: int) -> jax.Array:
    """The int32 bit pattern of each row's kth-largest |x|, shape (..., 1).

    Non-negative fp32 values order like their bit patterns, so the exact
    kth magnitude is the largest pattern t with count(bits >= t) >= k —
    built here one bit at a time, high to low (31 compare-and-count passes
    over the row).  Compares, selects and a lane sum are all Mosaic lowers,
    so the Pallas kernel runs this same function; ``lax.top_k`` does not
    lower inside a TPU kernel.  Counts are fp32 sums of 0/1, exact for rows
    up to 2^24 lanes."""
    bits = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32)

    def step(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        n = jnp.sum(jnp.where(bits >= cand, 1.0, 0.0), axis=-1,
                    keepdims=True)
        return jnp.where(n >= k, cand, t)

    t0 = jnp.zeros(x.shape[:-1] + (1,), jnp.int32)
    return jax.lax.fori_loop(0, 31, step, t0)


def ef_roundtrip(spec: CompressorSpec, x: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Dispatch ``ef_int8`` / ``ef_topk`` by spec over an (..., R, C)
    payload (fp32 in, fp32 out)."""
    if spec.name == "int8":
        return ef_int8(x)
    if spec.name == "topk":
        return ef_topk(x, topk_k(spec, x.shape[-1]))
    return x, jnp.zeros_like(x)          # "none": identity


def ef_leaf(spec: CompressorSpec, payload: jax.Array, n_lead: int,
            lanes: int = 256) -> Tuple[jax.Array, jax.Array]:
    """Per-leaf EF round-trip for the reference tree executor.

    ``payload``: fp32 with ``n_lead`` leading worker axes; the trailing
    leaf dims are raveled into rows of ``lanes`` (zero-padded tail).  Row
    grouping is leaf-aligned here but layout-aligned on the flat-buffer
    executors, so compressed reference-vs-fused trajectories agree only
    approximately — both are compared against the UNCOMPRESSED oracle.
    """
    lead = payload.shape[:n_lead]
    n = int(np.prod(payload.shape[n_lead:])) if payload.ndim > n_lead else 1
    u = used_rows(n, lanes)
    flat = payload.reshape(lead + (n,))
    pad = u * lanes - n
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * len(lead) + [(0, pad)])
    dec2, res2 = ef_roundtrip(spec, flat.reshape(lead + (u, lanes)))

    def back(b):
        return b.reshape(lead + (u * lanes,))[..., :n].reshape(payload.shape)

    return back(dec2), back(res2)


# ================================================================ wire format
class Int8Rep(NamedTuple):
    values: jax.Array            # (..., U, C) int8
    scales: jax.Array            # (..., U, 1) fp32


class TopKRep(NamedTuple):
    values: jax.Array            # (..., U, K) fp32
    indices: jax.Array           # (..., U, K) int32 lane offsets


class RawRep(NamedTuple):
    values: jax.Array            # (..., U, C) payload dtype


def compress(spec: CompressorSpec, x: jax.Array, *,
             rows_used: Optional[int] = None):
    """Payload (..., R, C) → the actual wire representation arrays.

    ``rows_used`` drops the trailing tile-padding rows (identically zero by
    the flat layout's construction) from the wire.
    """
    if rows_used is not None:
        x = x[..., :rows_used, :]
    x = x.astype(jnp.float32)
    if spec.name == "int8":
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = amax / 127.0
        safe = jnp.where(amax > 0, scale, 1.0)
        q = jnp.clip(jnp.round(x / safe), -127.0, 127.0)
        return Int8Rep(values=q.astype(jnp.int8), scales=scale)
    if spec.name == "topk":
        k = topk_k(spec, x.shape[-1])
        _, idx = jax.lax.top_k(jnp.abs(x), k)
        vals = jnp.take_along_axis(x, idx, axis=-1)
        return TopKRep(values=vals, indices=idx.astype(jnp.int32))
    return RawRep(values=x)


def decompress(spec: CompressorSpec, rep, *, rows: int,
               lanes: int) -> jax.Array:
    """Wire representation → the dense (..., R, C) fp32 payload (dropped
    tile-padding rows reconstructed as zeros)."""
    if spec.name == "int8":
        dec = rep.values.astype(jnp.float32) * rep.scales
    elif spec.name == "topk":
        v, idx = rep.values, rep.indices
        lead = v.shape[:-2]
        u, k = v.shape[-2:]
        v2 = v.reshape((-1, u, k))
        i2 = idx.reshape((-1, u, k))
        b = v2.shape[0]
        bi = jnp.arange(b)[:, None, None]
        ui = jnp.arange(u)[None, :, None]
        dec = jnp.zeros((b, u, lanes), jnp.float32).at[bi, ui, i2].set(v2)
        dec = dec.reshape(lead + (u, lanes))
    else:
        dec = rep.values.astype(jnp.float32)
    u = dec.shape[-2]
    if rows > u:
        pad = [(0, 0)] * (dec.ndim - 2) + [(0, rows - u), (0, 0)]
        dec = jnp.pad(dec, pad)
    return dec


def rep_nbytes(rep) -> int:
    """Measured wire bytes of an actual compressed representation."""
    return int(sum(a.size * jnp.dtype(a.dtype).itemsize
                   for a in jax.tree.leaves(rep)))


def raw_bytes(rows: int, lanes: int, itemsize: int = 4) -> int:
    """The uncompressed baseline: the full padded flat buffer the sync
    all-reduce carries today."""
    return rows * lanes * itemsize


def wire_bytes(spec: Optional[CompressorSpec], *, rows: int, lanes: int,
               size: Optional[int] = None, itemsize: int = 4) -> int:
    """One-way wire bytes for one (R, C) payload under ``spec``.

    ``size`` (real element count) enables the padding-row elision; the
    identity path transmits the raw buffer unchanged.  Matches
    ``rep_nbytes(compress(...))`` exactly.
    """
    if spec is None or is_identity(spec):
        return raw_bytes(rows, lanes, itemsize)
    u = used_rows(size, lanes) if size is not None else rows
    if spec.name == "int8":
        return u * lanes * 1 + u * 4
    if spec.name == "topk":
        k = topk_k(spec, lanes)
        return u * k * (4 + 4)
    raise ValueError(spec.name)


# ============================================================ metadata / ckpt
def meta(c) -> Optional[dict]:
    """JSON-safe description of one compressor (checkpoint validation)."""
    c = resolve(c)
    if c is None:
        return None
    return {"name": c.name, "rate": int(c.rate),
            "error_feedback": bool(c.error_feedback)}


def pair_meta(cfg_or_pair) -> Optional[dict]:
    """Per-level compressor metadata for a VRLConfig (or an explicit
    (level1, level2) pair); None when fully uncompressed."""
    if isinstance(cfg_or_pair, tuple):
        c1, c2 = cfg_or_pair
    else:
        c1, c2 = resolve_pair(cfg_or_pair)
    if c1 is None and c2 is None:
        return None
    return {"level1": meta(c1), "level2": meta(c2)}


def describe_pair(cfg_or_pair) -> str:
    """Human-readable per-level summary for launch banners."""
    if isinstance(cfg_or_pair, tuple):
        c1, c2 = cfg_or_pair
    else:
        c1, c2 = resolve_pair(cfg_or_pair)
    if c1 is None and c2 is None:
        return "none"
    l1 = c1.label() if c1 else "none"
    if c2 == c1 or c2 is None:
        return l1
    return f"{l1} / sync2={c2.label()}"
