"""The engine kernels compile for a TPU v5e, at qwen2-0.5b's row width.

Interpret mode (every other kernel test) runs a kernel body as Python and
never meets the TPU compiler's rules: block shapes tiled (8, 128) or equal
to the array's, a scoped-VMEM budget, the primitives Mosaic lowers.  These
tests compile each kernel that ``update_backend="auto"`` picks on a TPU for
a described (not attached) v5e chip, so a refusal shows here first.

Shapes: a (W=2, R, C=256) fp32 slice of qwen2-0.5b's flat buffer at the
engine's auto tile height of 1024 rows.  The compiler sees the same block
and grid layout as at the full 1.93M rows; only the grid is shorter.  One
more test compiles a whole training round at the published widths, with
the depth cut to two layers.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.configs.base import EngineConfig, VRLConfig
from repro.kernels import vrl_update as vu
from repro.train.train_loop import make_train_step

W, C, BLOCK = 2, 256, 1024
R = 240 * BLOCK             # divisible by the SM3 case's 4 shards
P_, D_ = 2, 1               # hierarchical grid: 2 pods of 1 worker


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from a persistent
    # cache, so keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _w(*lead, rows=R, lanes=C):
    return (*lead, rows, lanes)


KW = dict(block=BLOCK, interpret=False)

# name -> (kernel call, operand shapes); every operand is fp32
CASES = {
    "local_sgd": (
        lambda p, g, d: vu.fused_local_sgd(p, g, d, lr=0.05, **KW),
        [_w(W)] * 3),
    "local_momentum": (
        lambda p, g, d, m: vu.fused_local_momentum(
            p, g, d, m, lr=0.05, beta=0.9, **KW),
        [_w(W)] * 4),
    "local_adam": (
        lambda p, g, d, mu, nu, s: vu.fused_local_adam(
            p, g, d, mu, nu, s, lr=1e-3, **KW),
        [_w(W)] * 5 + [(1, 2)]),
    "local_adam_sm3_sharded": (
        lambda p, g, d, mu, row, col, s: vu.fused_local_adam_sm3(
            p, g, d, mu, row, col, s, lr=1e-3, **KW),
        [_w(W)] * 4 + [_w(W, lanes=1), (W, 4, C), (1, 2)]),
    "sync_vrl": (
        lambda p, x, d, s: vu.fused_sync_vrl(p, x, d, s, **KW),
        [_w(W), _w(), _w(W), (1, 1)]),
    "sync_bvr": (
        lambda p, x, d, b, s: vu.fused_sync_bvr(p, x, d, b, s, beta=0.5,
                                                **KW),
        [_w(W), _w(), _w(W), _w(W), (1, 1)]),
    "fold_overlap": (
        lambda p, x, pe, d, ws: vu.fused_fold_overlap(p, x, pe, d, ws, **KW),
        [_w(W), _w(), _w(W), _w(W), (W, 2)]),
    "fold_overlap_hier2": (
        lambda p, g, pe, d2, ws: vu.fused_fold_overlap_hier2(
            p, g, pe, d2, ws, **KW),
        [_w(P_, D_), _w(), _w(P_, 1), _w(P_, 1), (P_, 2)]),
    "ef_int8": (
        lambda p, r, e: vu.fused_ef_int8(p, r, e, **KW),
        [_w(W), _w(), _w(W)]),
    "ef_topk": (
        lambda p, r, e: vu.fused_ef_topk(p, r, e, k=C // 32, **KW),
        [_w(W), _w(), _w(W)]),
    "hier_local_adam": (
        lambda p, g, d1, d2, mu, nu, s: vu.fused_hier_local_adam(
            p, g, d1, d2, mu, nu, s, lr=1e-3, **KW),
        [_w(P_, D_)] * 3 + [_w(P_, 1)] + [_w(P_, D_)] * 2 + [(1, 2)]),
    "sync_hier1": (
        lambda p, x, d, s: vu.fused_sync_hier1(p, x, d, s, **KW),
        [_w(P_, D_), _w(P_, 1), _w(P_, D_), (1, 1)]),
    "sync_hier2": (
        lambda p, g, d2, s: vu.fused_sync_hier2(p, g, d2, s, **KW),
        [_w(P_, D_), _w(), _w(P_, 1), (1, 1)]),
}


def _kernel_line(hlo: str, kernel: str):
    """The compiled custom call named after ``kernel``'s ``pallas_call``
    (None where there is none)."""
    m = re.search(rf"^\s*(?:ROOT )?%{kernel}(?:\.\d+)? = .*"
                  r'custom_call_target="tpu_custom_call".*$', hlo, re.M)
    return m.group(0) if m else None


@pytest.mark.parametrize("name", sorted(CASES))
def test_engine_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, name
    # the custom call takes the kernel's name, which a profile shows
    kernel = "vrl_" + name.replace("_sharded", "").replace("sync_vrl",
                                                            "sync")
    assert _kernel_line(hlo, kernel), (name, kernel)


def test_bf16_moments_compile_for_v5e(one_chip):
    """bf16 moment buffers tile (16, 128) on the chip; the fp32 ones
    (8, 128) — the row tiles must suit both."""
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                             sharding=one_chip)
    fn = lambda p, g, d, mu, nu, s: vu.fused_local_adam(  # noqa: E731
        p, g, d, mu, nu, s, lr=1e-3, **KW)
    hlo = jax.jit(fn).lower(f32(_w(W)), f32(_w(W)), f32(_w(W)),
                            bf16(_w(W)), bf16(_w(W)),
                            f32((1, 2))).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_round_compiles_for_v5e_at_published_widths(one_chip):
    """One VRL-SGD round (k=2 local steps, then the sync) of qwen2-0.5b at
    its published widths, two of its 24 layers, one worker: the compiled
    local-step and sync kernels are both in the round program."""
    cfg = dataclasses.replace(registry.get_arch("qwen2-0.5b"), num_layers=2)
    vrl = VRLConfig(algorithm="vrl_sgd", comm_period=2, learning_rate=0.05,
                    warmup=False, update_backend="fused",
                    engine=EngineConfig(interpret=False))
    bundle = make_train_step(cfg, vrl)
    state = jax.eval_shape(lambda key: bundle.init_state(key, 1),
                           jax.random.PRNGKey(0))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), state)
    toks = jax.ShapeDtypeStruct((2, 1, 1, 512), jnp.int32, sharding=one_chip)
    hlo = jax.jit(bundle.round_step, donate_argnums=(0,)).lower(
        state, toks, toks).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 2
    # each kernel under its name, inside the engine's named scope
    for kernel, scope in (("vrl_local_sgd", "engine.local_update"),
                          ("vrl_sync", "engine.sync")):
        line = _kernel_line(hlo, kernel)
        assert line and f"{scope}/{kernel}/pallas_call" in line, kernel


def test_round_runs_flash_attention_for_v5e(one_chip):
    """The same two-layer qwen2-0.5b round at 2 x 2048 tokens: attention
    runs as the splash kernels (forward, and one backward for dq, dk, dv)
    inside the ``attention`` scope, and no (…, 2048, 2048) fp32 score
    tensor is left."""
    from repro.obs import scopemap

    cfg = dataclasses.replace(registry.get_arch("qwen2-0.5b"), num_layers=2)
    vrl = VRLConfig(algorithm="vrl_sgd", comm_period=2, learning_rate=0.05,
                    warmup=False, update_backend="fused",
                    engine=EngineConfig(interpret=False))
    bundle = make_train_step(cfg, vrl)
    state = jax.eval_shape(lambda key: bundle.init_state(key, 1),
                           jax.random.PRNGKey(0))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), state)
    toks = jax.ShapeDtypeStruct((2, 1, 2, 2048), jnp.int32,
                                sharding=one_chip)
    hlo = jax.jit(bundle.round_step, donate_argnums=(0,)).lower(
        state, toks, toks).compile().as_text()
    paths = scopemap.parse(hlo)
    # the forward, and the fused backward that gives dq, dk and dv
    kernels = {"splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"}
    found = {n for n in paths if n.split(".")[0] in kernels}
    assert {n.split(".")[0] for n in found} == kernels
    for name in found:
        assert _kernel_line(hlo, name.split(".")[0]), name
        assert "/attention/" in paths[name], (name, paths[name])
    # the forward twice (the forward pass and its remat), the backward
    # kernels once, all placed under ``attention``
    assert scopemap.attention_executor(paths) == {"executor": "flash",
                                                  "kernels": len(found)}
    assert len(found) == 3
    assert not re.search(r"f32\[[\d,]*2048,2048\]", hlo)


def test_mesh_round_keeps_dense_attention_for_v5e(topo):
    """Two workers on a two-chip mesh at 1024 tokens: the model runs
    partitioned by XLA, which cannot partition a Pallas kernel, so the
    round compiles with the dense attention core."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import engine as engine_mod
    from repro.launch import mesh as mesh_mod

    mesh = mesh_mod.make_engine_mesh(2, devices=list(topo.devices))
    cfg = dataclasses.replace(registry.get_arch("qwen2-0.5b"), num_layers=2,
                              d_model=128, num_heads=4, num_kv_heads=2,
                              d_ff=256, vocab_size=512)
    vrl = VRLConfig(algorithm="vrl_sgd", comm_period=2, learning_rate=0.05,
                    warmup=False, update_backend="fused",
                    engine=EngineConfig(interpret=False))
    axes = ("pod", "data")
    bundle = make_train_step(cfg, vrl, mesh=mesh, worker_axes=axes)
    state = jax.eval_shape(lambda key: bundle.init_state(key, 2),
                           jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        state, engine_mod.state_partition_specs(state, axes))
    toks = jax.ShapeDtypeStruct((2, 2, 1, 1024), jnp.int32,
                                sharding=NamedSharding(mesh, P(None, axes)))
    hlo = jax.jit(bundle.round_step, donate_argnums=(0,)).lower(
        state, toks, toks).compile().as_text()
    assert "splash_mha" not in hlo
    assert _kernel_line(hlo, "vrl_local_sgd")
