"""Flat-buffer layout for the algorithm engine.

The paper's per-step math (eqs. 4-6) is elementwise over *model-sized*
buffers, so its natural execution shape is not the parameter pytree but one
contiguous 2D buffer per worker: every leaf raveled, concatenated, padded to
a (rows, lanes) tile grid the Pallas kernels consume directly.  This module
owns that layout:

  * ``FlatSpec``     — the static unravel spec: leaf paths/shapes/dtypes with
                       their offsets into the flat vector, plus the chosen
                       (rows, lanes, block) tiling.  Hashable, and JSON
                       round-trippable for checkpoints.
  * ``make_spec``    — build a spec from a single-model template pytree
                       (concrete arrays or ShapeDtypeStructs).
  * flatten/unflatten — exact (pad/slice only, no arithmetic) conversions
                       between the pytree world and (R, C) / (W, R, C)
                       worker-stacked buffers, traced under the named
                       scopes ``flat.flatten`` / ``flat.unflatten``.

Tiling policy (``choose_block``): lanes are fixed at a VPU-friendly multiple
of 128; the row count is padded up to a multiple of the largest block in
{1024, 512, ..., 8} whose padding waste stays under ``max_waste`` — big
models get 1024-row tiles (one grid step per ~1 MiB of fp32), tiny ones
degrade gracefully instead of padding 8 elements up to a megabyte.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# Leaf paths use the checkpoint key style — share the formatter so the two
# can never diverge (save_flat_state metadata must match the array keys).
from repro.checkpoint.checkpoint import _path_str


_BLOCK_CANDIDATES = (1024, 512, 256, 128, 64, 32, 16, 8)


class LeafSpec(NamedTuple):
    path: str          # "/"-joined key path (matches checkpoint key style)
    shape: Tuple[int, ...]
    dtype: str
    offset: int        # element offset into the flat vector
    size: int


class FlatSpec(NamedTuple):
    treedef: Any                    # jax treedef of the single-model pytree
    leaves: Tuple[LeafSpec, ...]
    size: int                       # total real elements (sum of leaf sizes)
    lanes: int                      # C — last dim of the 2D buffer
    rows: int                       # R — padded row count (multiple of block)
    block: int                      # Pallas grid tile height
    dtype: str                      # buffer dtype for the params buffer
    shards: int = 1                 # model-axis shard count (rows % (block *
                                    # shards) == 0, so each shard holds whole
                                    # Pallas tiles and every tile stays local)

    @property
    def padded(self) -> int:
        return self.rows * self.lanes

    def meta(self) -> dict:
        """JSON-safe description (checkpoint validation / inspection)."""
        return {
            "leaves": [{"path": l.path, "shape": list(l.shape),
                        "dtype": l.dtype, "offset": l.offset, "size": l.size}
                       for l in self.leaves],
            "size": self.size, "lanes": self.lanes, "rows": self.rows,
            "block": self.block, "dtype": self.dtype, "shards": self.shards,
        }


def choose_block(rows: int, *, target: int = 1024,
                 max_waste: float = 0.25) -> int:
    """Largest candidate block whose row padding wastes <= ``max_waste``.

    Falls through to the smallest candidate when everything wastes more
    (tiny buffers) — matching the old hardcoded floor of 8 rows.
    """
    rows = max(int(rows), 1)
    for b in _BLOCK_CANDIDATES:
        if b > target:
            continue
        padded = -(-rows // b) * b
        if (padded - rows) / padded <= max_waste:
            return b
    return _BLOCK_CANDIDATES[-1]


def make_spec(template: Any, *, lanes: int = 256, block: int = 0,
              max_waste: float = 0.25, shards: int = 1) -> FlatSpec:
    """Build the unravel spec from a SINGLE-MODEL pytree template.

    ``template`` leaves may be arrays or ShapeDtypeStructs; only shapes and
    dtypes are read.  ``block=0`` selects the tile height automatically.
    ``shards`` pads rows up to a multiple of ``block * shards`` so the row
    axis splits into equal shards on tile boundaries — sharding only adds
    zero pad rows (inert through every update), never changes unflattened
    values, and ``shards=1`` reproduces the unsharded layout exactly.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    off = 0
    for path, leaf in flat:
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        leaves.append(LeafSpec(
            path="/".join(_path_str(p) for p in path),
            shape=tuple(int(s) for s in leaf.shape),
            dtype=str(jnp.dtype(leaf.dtype)), offset=off, size=size))
        off += size
    if not leaves:
        raise ValueError("empty template pytree")
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    dtype = str(jnp.result_type(*[np.dtype(l.dtype) for l in leaves]))
    rows_needed = -(-off // lanes)
    blk = int(block) if block else choose_block(rows_needed,
                                                max_waste=max_waste)
    quantum = blk * int(shards)
    rows = -(-rows_needed // quantum) * quantum
    return FlatSpec(treedef=treedef, leaves=tuple(leaves), size=off,
                    lanes=lanes, rows=rows, block=blk, dtype=dtype,
                    shards=int(shards))


def _check(spec: FlatSpec, tree: Any, stacked: bool):
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != len(spec.leaves):
        raise ValueError(f"tree has {len(leaves)} leaves, spec has "
                         f"{len(spec.leaves)}")
    lead = 1 if stacked else 0
    for got, want in zip(leaves, spec.leaves):
        if tuple(got.shape[lead:]) != want.shape:
            raise ValueError(f"leaf {want.path}: shape {got.shape} does not "
                             f"match spec {want.shape} (stacked={stacked})")
    return leaves


def flatten_tree(spec: FlatSpec, tree: Any,
                 dtype: Optional[Any] = None) -> jax.Array:
    """Single-model pytree -> (R, C) buffer.  Exact: pad-only."""
    leaves = _check(spec, tree, stacked=False)
    dt = jnp.dtype(dtype or spec.dtype)
    vec = jnp.concatenate([l.reshape(-1).astype(dt) for l in leaves])
    pad = spec.padded - spec.size
    if pad:
        vec = jnp.pad(vec, (0, pad))
    return vec.reshape(spec.rows, spec.lanes)


@jax.named_scope("flat.flatten")
def flatten_stacked(spec: FlatSpec, tree: Any,
                    dtype: Optional[Any] = None) -> jax.Array:
    """Worker-stacked pytree (leading axis W on every leaf) -> (W, R, C)."""
    leaves = _check(spec, tree, stacked=True)
    w = leaves[0].shape[0]
    dt = jnp.dtype(dtype or spec.dtype)
    vec = jnp.concatenate([l.reshape(w, -1).astype(dt) for l in leaves],
                          axis=1)
    pad = spec.padded - spec.size
    if pad:
        vec = jnp.pad(vec, ((0, 0), (0, pad)))
    return vec.reshape(w, spec.rows, spec.lanes)


def _gather_rows(buf: jax.Array, n_lead: int) -> jax.Array:
    """Drop a row/lane split that an Explicit-axis mesh carries in the
    buffer's type, keeping the ``n_lead`` worker dims' split: the leaf
    slices cross shard boundaries, which typed shardings refuse.  Auto-axis
    and meshless buffers pass through untouched."""
    s = jax.typeof(buf).sharding
    spec = tuple(s.spec) + (None,) * (buf.ndim - len(s.spec))
    if all(a is None for a in spec[n_lead:]):
        return buf
    keep = P(*spec[:n_lead], *([None] * (buf.ndim - n_lead)))
    return jax.sharding.reshard(buf, NamedSharding(s.mesh, keep))


@jax.named_scope("flat.unflatten")
def unflatten_tree(spec: FlatSpec, buf: jax.Array,
                   cast: bool = True) -> Any:
    """(R, C) buffer -> single-model pytree (leaf dtypes restored)."""
    vec = _gather_rows(buf, 0).reshape(-1)
    leaves = []
    for l in spec.leaves:
        piece = vec[l.offset:l.offset + l.size].reshape(l.shape)
        leaves.append(piece.astype(l.dtype) if cast else piece)
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


@jax.named_scope("flat.unflatten")
def unflatten_stacked(spec: FlatSpec, buf: jax.Array,
                      cast: bool = True) -> Any:
    """(W, R, C) buffer -> worker-stacked pytree ((W, ...) leaves)."""
    w = buf.shape[0]
    vec = _gather_rows(buf, 1).reshape(w, -1)
    leaves = []
    for l in spec.leaves:
        piece = vec[:, l.offset:l.offset + l.size].reshape((w,) + l.shape)
        leaves.append(piece.astype(l.dtype) if cast else piece)
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


# ------------------------------------------------- pod-major (P, D, R, C)
# The hierarchical engine carries its worker population as a pod-major grid:
# axis 0 indexes pods (slow cross-pod links), axis 1 the workers inside a
# pod (fast intra-pod links).  The flat layout per worker is IDENTICAL to
# the (W, R, C) one — a grid buffer is just the stacked buffer with its
# worker axis split (P, D) — so these are exact reshapes around the stacked
# converters and the same FlatSpec round-trips both.

@jax.named_scope("flat.flatten")
def flatten_grid(spec: FlatSpec, tree: Any,
                 dtype: Optional[Any] = None) -> jax.Array:
    """Grid-stacked pytree ((P, D, ...) leaves) -> (P, D, R, C)."""
    leaves = jax.tree_util.tree_leaves(tree)
    p, d = leaves[0].shape[:2]
    stacked = jax.tree.map(lambda x: x.reshape((p * d,) + x.shape[2:]), tree)
    buf = flatten_stacked(spec, stacked, dtype=dtype)
    return buf.reshape(p, d, spec.rows, spec.lanes)


@jax.named_scope("flat.unflatten")
def unflatten_grid(spec: FlatSpec, buf: jax.Array,
                   cast: bool = True) -> Any:
    """(P, D, R, C) buffer -> grid-stacked pytree ((P, D, ...) leaves)."""
    p, d, r, c = buf.shape
    tree = unflatten_stacked(spec, buf.reshape(p * d, r, c), cast=cast)
    return jax.tree.map(lambda x: x.reshape((p, d) + x.shape[1:]), tree)
