"""Plain float32 reference for a dense decoder LM trained by VRL-SGD.

Written from the published descriptions, not from the program: a
pre-norm decoder block (RMSNorm, rotary positions on the two halves of
each head, grouped-query causal attention with optional QKV bias, a
SwiGLU feed-forward), a final RMSNorm and a head tied to the embedding,
trained with next-token cross-entropy.  The optimizer is VRL-SGD
(Liang et al. 2019, Algorithm 1) over plain SGD with decoupled weight
decay: each worker i takes k local steps

    x_i <- x_i - lr * (g_i - delta_i + wd * x_i)

then every worker is set to the mean x_hat of the x_i, and

    delta_i <- delta_i + (x_hat - x_i) / (k * lr).

Everything is float32 under ``jax.default_matmul_precision("highest")``.
``dtype=jnp.bfloat16`` computes the same in bfloat16 (parameters,
activations and updates alike): the control, which the comparison must
reject.

The reference reads its sizes from the configuration file's top-level
keys (the published names) and makes the weights itself from a seed, so
the benchmark hands the same weights to the program and to this module.
It imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Dims(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    qkv_bias: bool
    eps: float
    theta: float


def dims(cfg: dict) -> Dims:
    """The sizes this reference needs, from a configuration file's
    top-level keys."""
    if cfg.get("hidden_act", "silu") != "silu" or not cfg.get(
            "tie_word_embeddings", False):
        raise ValueError("the dense reference covers SwiGLU with a tied head")
    return Dims(layers=int(cfg["num_hidden_layers"]),
                d=int(cfg["hidden_size"]),
                heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]),
                head_dim=int(cfg["head_dim"]),
                ff=int(cfg["intermediate_size"]),
                vocab=int(cfg["vocab_size"]),
                qkv_bias=bool(cfg.get("attention_bias", False)),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


# ------------------------------------------------------------------ weights
def shapes(m: Dims) -> dict:
    """Leaf name -> (shape, init, std).  Layer leaves carry a leading
    layer axis.  Fan-in scales: the contraction's width."""
    L, d, h, kv, hd, ff = m.layers, m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    attn = {"wq": ((L, d, h, hd), "normal", d ** -0.5),
            "wk": ((L, d, kv, hd), "normal", d ** -0.5),
            "wv": ((L, d, kv, hd), "normal", d ** -0.5),
            "wo": ((L, h, hd, d), "normal", (h * hd) ** -0.5)}
    if m.qkv_bias:
        attn.update(bq=((L, h, hd), "zeros", 0.0),
                    bk=((L, kv, hd), "zeros", 0.0),
                    bv=((L, kv, hd), "zeros", 0.0))
    return {
        "embed": ((m.vocab, d), "normal", 0.02),
        "final_norm": ((d,), "ones", 0.0),
        "layers": {
            "attn": attn,
            "mlp": {"w_gate": ((L, d, ff), "normal", d ** -0.5),
                    "w_up": ((L, d, ff), "normal", d ** -0.5),
                    "w_down": ((L, ff, d), "normal", ff ** -0.5)},
            "norm1": ((L, d), "ones", 0.0),
            "norm2": ((L, d), "ones", 0.0),
        },
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (64 bits are kept:
    ``PRNGKey`` alone drops the high word)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def params_from_key(m: Dims, key: jax.Array):
    """The benchmark's float32 weights from a PRNG key (traceable: the
    harness makes them inside the call that builds the program's
    state)."""
    leaves, treedef = jax.tree.flatten(shapes(m), is_leaf=_is_shape)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (shape, init, std), k in zip(leaves, keys):
        if init == "zeros":
            out.append(jnp.zeros(shape, jnp.float32))
        elif init == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(std * jax.random.normal(k, shape, jnp.float32))
    return jax.tree.unflatten(treedef, out)


def init_params(m: Dims, seed: int):
    """The benchmark's weights, made on the default device in one jitted
    call from ``seed``."""
    return jax.jit(functools.partial(params_from_key, m))(seed_key(seed))


def param_count(m: Dims) -> int:
    """Every trained parameter: the leaves this reference makes, the
    tied embedding once."""
    return int(sum(np.prod(s) for s, _, _ in jax.tree.leaves(
        shapes(m), is_leaf=_is_shape)))


def matmul_params(m: Dims) -> int:
    """Weights that enter a matrix product per token: the attention
    projections, the SwiGLU matrices and the tied head.  The embedding
    gather is no product; biases and norm scales are not matrices."""
    q = m.d * m.heads * m.head_dim
    kv = 2 * m.d * m.kv_heads * m.head_dim
    o = m.heads * m.head_dim * m.d
    mlp = 3 * m.d * m.ff
    return m.layers * (q + kv + o + mlp) + m.vocab * m.d


def train_flops_per_token(m: Dims, seq: int) -> int:
    """FLOP one token needs in a training step, forward and backward:
    6 per matmul weight, plus causal attention's 6 * L * S * H * hd
    (scores and the weighted sum, each 2 * S * H * hd for the full
    square forward, halved by the causal mask, tripled by the backward).
    Recomputation under remat is not required, so it is not counted."""
    return (6 * matmul_params(m)
            + 6 * m.layers * seq * m.heads * m.head_dim)


# ------------------------------------------------------------------ forward
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd).  Rotates the pairs (x[j], x[j + hd/2]) by
    position * theta^(-2j/hd)."""
    s, _, hd = x.shape
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), x.dtype)[:, None, :]
    sin = jnp.asarray(np.sin(ang), x.dtype)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _layer(m: Dims, x, p):
    s = x.shape[0]
    h = _rms(x, p["norm1"], m.eps)
    a = p["attn"]
    q = jnp.einsum("sd,dhk->shk", h, a["wq"])
    k = jnp.einsum("sd,dhk->shk", h, a["wk"])
    v = jnp.einsum("sd,dhk->shk", h, a["wv"])
    if m.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, m.theta), _rope(k, m.theta)
    g = m.heads // m.kv_heads
    # query head j reads key/value head j // g
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhk,shk->hqs", q, k) * jnp.asarray(
        m.head_dim ** -0.5, x.dtype)
    causal = np.tril(np.ones((s, s), bool))
    sc = jnp.where(causal[None], sc, jnp.asarray(-jnp.inf, sc.dtype))
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqs,shk->qhk", pr, v)
    x = x + jnp.einsum("qhk,hkd->qd", o, a["wo"])
    h = _rms(x, p["norm2"], m.eps)
    f = p["mlp"]
    return x + (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]


def sequence_loss(m: Dims, params, tokens, labels):
    """Mean next-token cross-entropy of one sequence (S,) -> scalar."""
    x = params["embed"][tokens]

    def body(x, p):
        return _layer(m, x, p), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    x = _rms(x, params["final_norm"], m.eps)
    logits = (x @ params["embed"].T).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, labels[:, None],
                                              axis=-1)[:, 0])


# ------------------------------------------------------------- VRL-SGD round
# The program's settings (``VRLConfig`` fields) this reference follows:
# VRL-SGD over plain SGD with a blocking sync every k steps.  A traffic
# file that sets any of them otherwise names a reference of its own.
FOLLOWS = dict(algorithm="vrl_sgd", inner_optimizer="sgd", momentum=0.0,
               clip_norm=0.0, warmup=False, comm_schedule=None,
               delta_dtype="float32", hier=None, compress=None,
               compress2=None, overlap=False, deadline=0.0,
               membership=False)


def check(vrl) -> None:
    """Raises ValueError where the program runs settings this reference
    does not follow."""
    off = {k: getattr(vrl, k) for k, v in FOLLOWS.items()
           if getattr(vrl, k) != v}
    if off:
        raise ValueError(f"the dense VRL-SGD reference follows {FOLLOWS}; "
                         f"the program runs {off}")


class Readings(NamedTuple):
    """What the reference reports of the rounds it follows."""
    losses: np.ndarray          # (steps,) mean over workers, per step
    update_norms: list          # per round: (leaves,) norms of x_hat - x0
    delta_norms: list           # per round: (W, leaves) norms of delta_i
    delta_sum_norms: list       # per round: (leaves,) norms of sum_i delta_i
    drift: list                 # per round: max |x_i - x_0| over workers


def leaf_norms(tree) -> np.ndarray:
    return np.array([float(jnp.sqrt(jnp.sum(jnp.square(
        leaf.astype(jnp.float32))))) for leaf in jax.tree.leaves(tree)])


class Reference:
    """VRL-SGD over W workers, one round at a time, with the learning
    rate and weight decay of the program's ``vrl``.  Worker i's state
    lives on ``devices[i % len(devices)]``; ``dtype`` is float32 (the
    reference) or bfloat16 (the control).  ``half_batch`` takes each
    loss over the first half of the batch's rows (of the sequence, for
    one row) and ``no_exchange`` skips the mean: both are faults, for the
    readings that set the limits."""

    def __init__(self, m: Dims, vrl, *, workers: int, devices,
                 dtype=jnp.float32, half_batch: bool = False,
                 no_exchange: bool = False):
        check(vrl)
        lr, wd = vrl.learning_rate, vrl.weight_decay
        self.m, self.lr, self.wd, self.w = m, lr, wd, workers
        self.devices = list(devices)
        self.dtype = jnp.dtype(dtype)
        self.half_batch, self.no_exchange = half_batch, no_exchange
        prec = "highest" if self.dtype == jnp.float32 else "default"

        def grad(params, tok, lab):
            with jax.default_matmul_precision(prec):
                return jax.value_and_grad(
                    functools.partial(sequence_loss, m))(params, tok, lab)

        def grad_acc(params, acc, tok, lab):
            loss, g = grad(params, tok, lab)
            return loss, (g if acc is None
                          else jax.tree.map(jnp.add, acc, g))

        self._prec = prec
        self._grad = jax.jit(grad_acc, donate_argnums=(1,))
        dt = self.dtype

        def step(p, gsum, d, nrows):
            def one(x, g, dl):
                v = g / nrows + wd * x
                if dl is not None:
                    v = v - dl
                return (x - jnp.asarray(lr, dt) * v).astype(dt)
            if d is None:
                return jax.tree.map(lambda x, g: one(x, g, None), p, gsum)
            return jax.tree.map(one, p, gsum, d)

        self._step = jax.jit(step)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self._maxdiff = jax.jit(lambda a, b: jnp.max(jnp.stack(
            [jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])))

    def _dev(self, i):
        return self.devices[i % len(self.devices)]

    def _rows(self, tokens, labels):
        """The (tokens, labels) sequences one loss is the mean over."""
        if not self.half_batch:
            return list(zip(tokens, labels))
        if tokens.shape[0] > 1:
            h = tokens.shape[0] // 2
            return list(zip(tokens[:h], labels[:h]))
        s = tokens.shape[1] // 2
        return [(tokens[0, :s], labels[0, :s])]

    def run(self, params0, rounds) -> Readings:
        """Follow ``rounds``: a list of (k, W, B, S) int token arrays (the
        labels are the tokens rolled by one along the sequence).  With one
        worker the mean is that worker, so delta stays exactly zero and is
        not stored."""
        with jax.default_matmul_precision(self._prec):
            return self._run(params0, rounds)

    def _run(self, params0, rounds) -> Readings:
        dt, w = self.dtype, self.w
        x0 = jax.tree.map(lambda a: a.astype(dt), params0)
        ps = [jax.device_put(x0, self._dev(i)) for i in range(w)]
        ds = [None] * w
        losses, upd, dn, dsn, drift = [], [], [], [], []
        for toks in rounds:
            toks = np.asarray(toks)
            labs = np.roll(toks, -1, axis=-1)
            k = toks.shape[0]
            for t in range(k):
                step_loss = 0.0
                for i in range(w):
                    rows = self._rows(toks[t, i], labs[t, i])
                    gsum, lsum = None, 0.0
                    for tok, lab in rows:
                        loss, gsum = self._grad(
                            ps[i], gsum, jax.device_put(tok, self._dev(i)),
                            jax.device_put(lab, self._dev(i)))
                        lsum += float(loss)
                    ps[i] = self._step(ps[i], gsum, ds[i],
                                       jnp.asarray(len(rows), dt))
                    del gsum
                    step_loss += lsum / len(rows)
                losses.append(step_loss / w)
            if w > 1 and not self.no_exchange:
                xhat = jax.device_put(ps[0], self._dev(0))
                for i in range(1, w):
                    xhat = self._add(xhat, jax.device_put(ps[i],
                                                          self._dev(0)))
                xhat = jax.tree.map(lambda a: (a.astype(jnp.float32) / w
                                               ).astype(dt), xhat)
                kg = jnp.asarray(k * self.lr, dt)
                for i in range(w):
                    xi = jax.device_put(xhat, self._dev(i))
                    ds[i] = jax.tree.map(
                        lambda xh, x: (xh - x) / kg, xi, ps[i]) \
                        if ds[i] is None else jax.tree.map(
                        lambda d, xh, x: (d + (xh - x) / kg).astype(dt),
                        ds[i], xi, ps[i])
                    ps[i] = xi
                del xhat
            upd.append(leaf_norms(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                ps[0], jax.device_put(x0, self._dev(0)))))
            n_leaves = len(upd[-1])
            drift.append(max([0.0] + [float(self._maxdiff(
                jax.device_put(ps[i], self._dev(0)), ps[0]))
                for i in range(1, w)]))
            dn.append(np.stack([np.zeros(n_leaves) if d is None
                                else leaf_norms(d) for d in ds]))
            if ds[0] is None:
                dsn.append(np.zeros(n_leaves))
                continue
            total = jax.device_put(ds[0], self._dev(0))
            for i in range(1, w):
                total = self._add(total, jax.device_put(ds[i], self._dev(0)))
            dsn.append(leaf_norms(total))
        return Readings(np.array(losses), upd, dn, dsn, drift)

