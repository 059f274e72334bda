"""Which attention core ``attend_full`` runs, and that the two agree.

A TPU lowering at contiguous positions from ``FLASH_MIN_SEQ`` tokens on runs
the flash kernel; the CPU backend, shorter sequences and explicit positions
run the dense fp32-score path.  Here ``FLASH_MIN_SEQ`` is lowered to the
test's short sequence, the TPU branch is forced through
``jax.lax.platform_dependent`` and the kernel runs in interpret mode, so the
flash branch is checked on the CPU.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.models import attention
from repro.models import transformer as T
from repro.models.layers import apply_rope
from repro.models.param import materialize

S = 160         # not a multiple of the kernel's block: the wrapper pads


def _cfg(heads=4, kv_heads=2):
    return dataclasses.replace(registry.get_arch("qwen2-0.5b"), num_layers=2,
                               d_model=128, num_heads=heads,
                               num_kv_heads=kv_heads, head_dim=64, d_ff=256,
                               vocab_size=256)


def _attn_inputs(cfg, seed=0):
    p = materialize(attention.attention_defs(cfg), jax.random.PRNGKey(seed),
                    jnp.float32)
    p = jax.tree.map(lambda a: a + 0.02, p)     # nonzero QKV biases
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, S, cfg.d_model))
    return p, x


@pytest.fixture
def short_flash(monkeypatch):
    """Contiguous calls of ``S`` tokens dispatch to the flash branch."""
    monkeypatch.setattr(attention, "FLASH_MIN_SEQ", S)


@pytest.fixture
def tpu_branch(monkeypatch, short_flash):
    """``platform_dependent`` takes its ``tpu`` branch; the kernel runs in
    interpret mode."""
    real = attention.ops.mha_flash
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    monkeypatch.setattr(attention.ops, "mha_flash", lambda *a, **kw: real(
        *a, **{**kw, "interpret": True}))


@pytest.mark.parametrize("window", [None, 64])
def test_flash_branch_matches_dense_on_bf16_operands(tpu_branch, window):
    """The flash branch against the dense branch given the same operands
    rounded to bf16, forward and gradients, under ``jax.checkpoint``."""
    cfg = _cfg()
    p, x = _attn_inputs(cfg)
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    scale = cfg.head_dim ** -0.5
    group = cfg.num_heads // cfg.num_kv_heads

    def qkv(p):
        q, k, v = attention._project_qkv(cfg, p, x)
        return (apply_rope(q, pos, cfg.rope_theta),
                apply_rope(k, pos, cfg.rope_theta), v)

    def flash(p):
        return attention._flash_core(window, scale, jnp.float32, *qkv(p))

    def dense_bf16(p):
        q, k, v = qkv(p)
        q, k, v = (t.astype(jnp.bfloat16).astype(jnp.float32)
                   for t in (q * scale, k, v))
        return attention._dense_core(pos, window, group, 1.0, jnp.float32,
                                     q, k, v)

    g = jax.random.normal(jax.random.PRNGKey(7), (2, S, cfg.num_heads,
                                                  cfg.head_dim))

    def run(core):
        return jax.value_and_grad(
            lambda p: (jax.checkpoint(core)(p) * g).sum())(p), core(p)

    (lf, gf), of = run(flash)
    (ld, gd), od = run(dense_bf16)
    # bf16 output and bf16 probabilities in the kernel's P.V: a few bf16
    # ulps (2^-8) of the largest element
    assert float(jnp.max(jnp.abs(of - od))) < 2e-2 * float(
        jnp.max(jnp.abs(od)))
    assert abs(float(lf - ld)) < 2e-2 * abs(float(ld))
    for name in ("wq", "wk", "wv", "bq", "bk", "bv"):
        a, r = gf[name], gd[name]
        err = float(jnp.max(jnp.abs(a - r)) / jnp.max(jnp.abs(r)))
        assert err < 2e-2, (name, err)

    # attend_full dispatches contiguous positions to that branch
    out = attention.attend_full(cfg, p, x, pos, window=window,
                                contiguous=True)
    want = jnp.einsum("...qhk,hkd->...qd", of, p["wo"])
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5


def _has_kernel(fn, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def test_explicit_positions_keep_the_dense_path(monkeypatch):
    """``forward`` without positions stages the flash kernel (on a TPU
    lowering); explicit positions, the cache-building prefill, and a
    sequence shorter than ``FLASH_MIN_SEQ`` do not."""
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              cfg.vocab_size)
    assert S < attention.FLASH_MIN_SEQ
    assert not _has_kernel(lambda p, t: T.forward(cfg, p, t), params, toks)
    monkeypatch.setattr(attention, "FLASH_MIN_SEQ", S)
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    assert _has_kernel(lambda p, t: T.forward(cfg, p, t), params, toks)
    assert not _has_kernel(lambda p, t: T.forward(cfg, p, t, positions=pos),
                           params, toks)
    assert not _has_kernel(lambda p, t: T.prefill(cfg, p, t, cache_len=S),
                           params, toks)


def _canonical(hlo: str) -> str:
    """Compiled HLO without metadata, debug tables and instruction
    numbers."""
    hlo = hlo.split("\nFileNames")[0]
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    return re.sub(r"\.\d+\b", "", hlo)


def test_cpu_lowering_is_the_dense_path(short_flash):
    """On the CPU backend the contiguous call compiles to the very program
    of the explicit-positions (dense) call, gradients included."""
    cfg = _cfg()
    p, x = _attn_inputs(cfg)
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))

    def compiled(contiguous):
        def loss(p):
            return jax.checkpoint(lambda p: attention.attend_full(
                cfg, p, x, pos, contiguous=contiguous))(p).sum()
        return jax.jit(jax.grad(loss)).lower(p).compile().as_text()

    assert _canonical(compiled(True)) == _canonical(compiled(False))
