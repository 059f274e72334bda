"""Hypothesis property tests for the Pallas kernels (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels.ref import flash_attention_ref, ssd_ref


@settings(max_examples=12, deadline=None)
@given(b=st.integers(1, 2), s=st.integers(3, 96), h=st.integers(1, 3),
       kv_ratio=st.sampled_from([1, 2]), d=st.sampled_from([16, 32]),
       window=st.sampled_from([None, 16]),
       seed=st.integers(0, 1000))
def test_mha_flash_matches_reference(b, s, h, kv_ratio, d, window, seed):
    """Arbitrary (non-aligned!) shapes: the wrapper pads to block multiples
    and must still match plain softmax attention exactly; kv heads are
    read in groups, not repeated."""
    nh = h * kv_ratio
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, nh, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    out = ops.mha_flash(q, k, v, window=window, block_q=128, block_k=128)
    kk = jnp.repeat(k, kv_ratio, axis=2)
    vv = jnp.repeat(v, kv_ratio, axis=2)
    ref = flash_attention_ref(
        jnp.moveaxis(q, 2, 1).reshape(b * nh, s, d),
        jnp.moveaxis(kk, 2, 1).reshape(b * nh, s, d),
        jnp.moveaxis(vv, 2, 1).reshape(b * nh, s, d), window=window)
    ref = jnp.moveaxis(ref.reshape(b, nh, s, d), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 2), nc=st.integers(1, 4), h=st.integers(1, 3),
       p=st.sampled_from([8, 16]), n=st.sampled_from([4, 8]),
       seed=st.integers(0, 1000))
def test_ssd_scan_matches_recurrence(b, nc, h, p, n, seed):
    """Chunked SSD == sequential recurrence for arbitrary chunk counts."""
    chunk = 16
    l = nc * chunk
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    bb = jax.random.normal(ks[3], (b, l, n)) * 0.3
    cc = jax.random.normal(ks[4], (b, l, n)) * 0.3
    y = ops.ssd_chunk_scan(x, dt, a_log, bb, cc, chunk=chunk)
    yr = ssd_ref(x, dt, a_log, bb, cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=5e-3, atol=5e-4)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 500), lr=st.floats(1e-4, 1.0),
       seed=st.integers(0, 1000))
def test_vrl_update_arbitrary_sizes(n, lr, seed):
    """The fused update handles any flattened size via padding."""
    from repro.kernels.ref import vrl_update_ref
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = jax.random.normal(ks[0], (n,))
    g = jax.random.normal(ks[1], (n,))
    d = jax.random.normal(ks[2], (n,))
    out = ops.vrl_local_update_tree({"w": p}, {"w": g}, {"w": d}, lr=lr)
    ref = vrl_update_ref(p, g, d, lr)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
