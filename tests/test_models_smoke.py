"""Per-architecture smoke tests: REDUCED same-family variants (2 layers,
d_model<=512, <=4 experts) run one forward + one train step + one decode
step on CPU, asserting output shapes and no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.configs.base import VRLConfig
from repro.models import transformer as T
from repro.train.train_loop import make_train_step

ALL_ARCHS = registry.list_archs()


def make_inputs(cfg, batch, seq, key):
    if cfg.frontend == "codec":
        return jax.random.normal(key, (batch, seq, cfg.frontend_dim))
    return jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = registry.smoke_arch(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    inp = make_inputs(cfg, 2, 32, jax.random.PRNGKey(1))
    logits, aux = jax.jit(lambda p, x: T.forward(cfg, p, x))(params, inp)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step(arch):
    cfg = registry.smoke_arch(arch)
    vrl = VRLConfig(comm_period=2, learning_rate=0.01, warmup=False)
    bundle = make_train_step(cfg, vrl, remat=False)
    state = bundle.init_state(jax.random.PRNGKey(0), num_workers=2)
    key = jax.random.PRNGKey(1)
    if cfg.frontend == "codec":
        tokens = jax.random.normal(key, (2, 2, 32, cfg.frontend_dim))
    else:
        tokens = jax.random.randint(key, (2, 2, 32), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 2, 32), 0,
                                cfg.vocab_size)
    new_state, loss = jax.jit(bundle.train_step)(state, tokens, labels)
    assert bool(jnp.isfinite(loss))
    assert int(new_state.step) == 1
    # params actually moved
    moved = any(
        float(jnp.max(jnp.abs(a - b))) > 0
        for a, b in zip(jax.tree.leaves(new_state.params),
                        jax.tree.leaves(state.params)))
    assert moved


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_decode_step(arch):
    cfg = registry.smoke_arch(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cache = T.init_cache(cfg, 2, 16, dtype=jnp.float32)
    tok = make_inputs(cfg, 2, 1, jax.random.PRNGKey(1))
    logits, new_cache = jax.jit(
        lambda p, c, t: T.decode_step(cfg, p, c, t, 0))(params, cache, tok)
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


@pytest.mark.parametrize("arch", ["granite-3-2b", "hymba-1.5b", "mamba2-370m"])
def test_prefill_then_decode_continuation(arch):
    """prefill() cache must continue exactly like step-by-step decode."""
    cfg = registry.smoke_arch(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    cache_len = 16
    logits_pf, cache_pf = T.prefill(cfg, params, toks, cache_len)

    cache = T.init_cache(cfg, 1, cache_len, dtype=jnp.float32)
    for i in range(8):
        logits_st, cache = T.decode_step(cfg, params, cache, toks[:, i:i + 1], i)
    err = float(jnp.max(jnp.abs(logits_pf[:, -1] - logits_st[:, 0])))
    assert err < 5e-4, err
    # continue one token from both caches: must agree
    nxt = jnp.argmax(logits_st[:, -1:], -1).astype(jnp.int32)
    l1, _ = T.decode_step(cfg, params, cache_pf, nxt, 8)
    l2, _ = T.decode_step(cfg, params, cache, nxt, 8)
    assert float(jnp.max(jnp.abs(l1 - l2))) < 5e-4


def test_init_gradient_does_not_grow_with_depth():
    """Each projection starts at its contraction's fan-in scale, so a deep
    stack's gradient at init stays the size of a shallow one's.  Attention
    weights scaled by a head count instead grow it by orders of magnitude
    every few layers: SGD at lr 0.05 then turns qwen2-0.5b's 24 layers to
    NaN in one step."""
    from repro.train.loss import cross_entropy_lm
    cfg = registry.smoke_arch("qwen2-0.5b")
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0,
                              cfg.vocab_size)

    def grad_norm(layers):
        c = dataclasses.replace(cfg, num_layers=layers)
        g = jax.grad(lambda p: cross_entropy_lm(
            T.forward(c, p, toks[:, :-1])[0], toks[:, 1:]))(
                T.init_params(c, jax.random.PRNGKey(0)))
        return float(jnp.sqrt(sum(jnp.sum(x * x)
                                  for x in jax.tree.leaves(g))))

    shallow, deep = grad_norm(2), grad_norm(12)
    assert deep < 2 * shallow, (shallow, deep)
