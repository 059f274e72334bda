"""Pallas kernel allclose tests: shape/dtype sweeps against pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import (
    flash_attention_ref,
    ssd_ref,
    vrl_sync_ref,
    vrl_update_ref,
)
from repro.kernels.ssd_scan import ssd_scan


@pytest.mark.parametrize("bh,s,d", [(2, 256, 64), (4, 128, 128), (1, 512, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(bh, s, d, dtype):
    key = jax.random.PRNGKey(bh * s + d)
    q, k, v = (jax.random.normal(kk, (bh, s, d)).astype(dtype)
               for kk in jax.random.split(key, 3))
    out = flash_attention(q, k, v, block_q=128 if s >= 128 else s,
                          block_k=128 if s >= 128 else s)
    ref = flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < tol


@pytest.mark.parametrize("window", [None, 64, 128])
def test_flash_attention_window(window):
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (2, 256, 64))
               for kk in jax.random.split(key, 3))
    out = flash_attention(q, k, v, window=window, block_q=128, block_k=128)
    ref = flash_attention_ref(q, k, v, window=window)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_flash_attention_block_shape_independence():
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(kk, (2, 256, 64))
               for kk in jax.random.split(key, 3))
    o1 = flash_attention(q, k, v, block_q=128, block_k=256)
    o2 = flash_attention(q, k, v, block_q=256, block_k=128)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-5


def _dense_gqa(q, k, v, window):
    """(B, S, H, D) q, (B, S, KVH, D) k, v through the plain oracle, kv
    heads repeated to H."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    heads = lambda t: jnp.moveaxis(t, 2, 1).reshape(b * h, s, d)  # noqa: E731
    out = flash_attention_ref(heads(q), heads(jnp.repeat(k, group, axis=2)),
                              heads(jnp.repeat(v, group, axis=2)),
                              window=window)
    return jnp.moveaxis(out.reshape(b, h, s, d), 1, 2)


@pytest.mark.parametrize("h,kvh", [(14, 2), (32, 8)])
@pytest.mark.parametrize("window", [None, 96])
def test_flash_attention_gradients_gqa(h, kvh, window):
    """dq, dk, dv of the kernel's backward (kv heads read in groups of 7
    and 4, not repeated) against jax.grad of the dense oracle; S=200 is
    padded to the block."""
    ks = jax.random.split(jax.random.PRNGKey(h + (window or 0)), 4)
    b, s, d = 1, 200, 64
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kvh, d))
    v = jax.random.normal(ks[2], (b, s, kvh, d))
    g = jax.random.normal(ks[3], (b, s, h, d))

    def grads(core):
        return jax.grad(lambda q, k, v: (core(q, k, v) * g).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: ops.mha_flash(q, k, v, window=window))
    want = grads(lambda q, k, v: _dense_gqa(q, k, v, window))
    for name, a, r in zip("qkv", got, want):
        assert a.shape == r.shape, name
        err = float(jnp.max(jnp.abs(a - r)) / jnp.max(jnp.abs(r)))
        assert err < 2e-5, (name, err)


@pytest.mark.parametrize("b,l,h,p,n,chunk", [
    (1, 64, 2, 16, 8, 16), (2, 128, 4, 32, 16, 32), (1, 256, 1, 64, 128, 64)])
def test_ssd_scan_shapes(b, l, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(l + h), 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    bb = jax.random.normal(ks[3], (b, l, n)) * 0.3
    cc = jax.random.normal(ks[4], (b, l, n)) * 0.3
    y = ops.ssd_chunk_scan(x, dt, a_log, bb, cc, chunk=chunk)
    yr = ssd_ref(x, dt, a_log, bb, cc)
    assert float(jnp.max(jnp.abs(y - yr))) < 5e-3


def test_ssd_scan_bf16():
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    b, l, h, p, n = 2, 64, 2, 32, 16
    x = jax.random.normal(ks[0], (b, l, h, p)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))).astype(jnp.bfloat16)
    a_log = (jax.random.normal(ks[2], (h,)) * 0.5)
    bb = (jax.random.normal(ks[3], (b, l, n)) * 0.3).astype(jnp.bfloat16)
    cc = (jax.random.normal(ks[4], (b, l, n)) * 0.3).astype(jnp.bfloat16)
    y = ops.ssd_chunk_scan(x, dt, a_log, bb, cc, chunk=32)
    yr = ssd_ref(x.astype(jnp.float32), dt.astype(jnp.float32), a_log,
                 bb.astype(jnp.float32), cc.astype(jnp.float32))
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - yr))) < 0.15


def test_ssd_matches_model_chunked_path():
    """The Pallas kernel and the model's jnp chunked path agree."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    b, l, h, p, n = 2, 128, 4, 32, 16
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    bb = jax.random.normal(ks[3], (b, l, n)) * 0.3
    cc = jax.random.normal(ks[4], (b, l, n)) * 0.3
    y1 = ops.ssd_chunk_scan(x, dt, a_log, bb, cc, chunk=32)
    y2 = ssd_chunked(x, dt, a_log, bb, cc, chunk=32)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-4


@pytest.mark.parametrize("shape", [(64, 64), (1000,), (3, 5, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vrl_local_update_tree(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(sum(shape)), 3)
    p = jax.random.normal(ks[0], shape).astype(dtype)
    g = jax.random.normal(ks[1], shape).astype(dtype)
    d = jax.random.normal(ks[2], shape)
    out = ops.vrl_local_update_tree({"w": p}, {"w": g}, {"w": d}, lr=0.03)
    ref = vrl_update_ref(p, g, d, 0.03)
    assert float(jnp.max(jnp.abs(out["w"].astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < 2e-2


def test_vrl_sync_update_tree():
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    p = jax.random.normal(ks[0], (130, 7))
    xb = jax.random.normal(ks[1], (130, 7))
    d = jax.random.normal(ks[2], (130, 7))
    po, do = ops.vrl_sync_update_tree({"w": p}, {"w": xb}, {"w": d},
                                      k=10, lr=0.05)
    rp, rd = vrl_sync_ref(p, xb, d, 1.0 / (10 * 0.05))
    assert float(jnp.max(jnp.abs(po["w"] - rp))) < 1e-6
    assert float(jnp.max(jnp.abs(do["w"] - rd))) < 1e-5
