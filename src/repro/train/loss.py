"""Losses: causal LM cross-entropy (fp32 logsumexp) and classifier CE.

The LM losses run under the named scope ``head``, with the final norm and
the vocabulary product of ``models/transformer.py``."""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("head")
def cross_entropy_lm(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """logits (..., S, V) vs next-token labels (..., S) — mean NLL."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def cross_entropy_cls(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """logits (..., C) vs labels (...,) — mean NLL."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))


@jax.named_scope("head")
def chunked_cross_entropy_lm(hidden: jax.Array, head: jax.Array,
                             labels: jax.Array, chunk: int = 8192,
                             head_is_embed: bool = False) -> jax.Array:
    """Vocab-streaming CE: never materializes the (..., V) logits.

    hidden: (..., S, d) post-final-norm activations;
    head: (d, V) lm head, or (V, d) tied embedding with head_is_embed=True;
    labels: (..., S). Computes a running logsumexp over vocab chunks with a
    lax.scan — peak memory O(S * chunk) instead of O(S * V). At gemma-7b's
    256k vocab this removes a ~10x-seq-length fp32 buffer from the loss.
    """
    if head_is_embed:
        head = head.T                                  # (d, V)
    d, v = head.shape
    pad = (-v) % chunk
    if pad:
        head = jnp.pad(head, ((0, 0), (0, pad)), constant_values=0.0)
    nv = (v + pad) // chunk
    h32 = hidden.astype(jnp.float32)
    lead = hidden.shape[:-1]

    def body(carry, i):
        m, s, ll = carry
        w_c = jax.lax.dynamic_slice_in_dim(head, i * chunk, chunk, axis=1)
        logits_c = h32 @ w_c.astype(jnp.float32)       # (..., chunk)
        if pad:  # mask padded vocab rows
            col = i * chunk + jnp.arange(chunk)
            logits_c = jnp.where(col < v, logits_c, -1e30)
        m_c = jnp.max(logits_c, axis=-1)
        m_new = jnp.maximum(m, m_c)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits_c - m_new[..., None]), axis=-1)
        # label logit if it falls in this chunk
        in_chunk = (labels >= i * chunk) & (labels < (i + 1) * chunk)
        idx = jnp.clip(labels - i * chunk, 0, chunk - 1)
        lab_logit = jnp.take_along_axis(logits_c, idx[..., None], -1)[..., 0]
        ll = jnp.where(in_chunk, lab_logit, ll)
        return (m_new, s, ll), None

    init = (jnp.full(lead, -1e30, jnp.float32),
            jnp.zeros(lead, jnp.float32),
            jnp.zeros(lead, jnp.float32))
    (m, s, ll), _ = jax.lax.scan(body, init, jnp.arange(nv))
    lse = m + jnp.log(jnp.maximum(s, 1e-30))
    return jnp.mean(lse - ll)
