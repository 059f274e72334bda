"""Distributed train-step factory.

``make_train_step`` binds (model config, algorithm config) into three jittable
functions over worker-stacked state:

  train_step(state, tokens, labels) -> (state, loss)
      one local iteration + conditional sync (the paper's Algorithm 1 body)
  local_step(state, tokens, labels) -> (state, loss)
      local iteration only — zero worker-axis collectives (dry-run accounting)
  sync_step(state) -> state
      model averaging + Δ update only (the per-period communication event)
  round_step(state, tokens_k, labels_k) -> (state, losses)
      ONE COMMUNICATION ROUND as a single compilation unit: k local steps
      under a ``lax.scan`` over (k, W, ...) token/label stacks — losses
      buffered device-side, no per-step python dispatch or host sync —
      followed by the round-closing sync.  Compiled once per (k, shape);
      jit with ``donate_argnums=(0,)`` so the state updates in place.
      Hierarchical: the round is one k1 period and the level-2 sync fires
      on its k2 cadence inside round_step (requires k2 % k1 == 0).
      Warmup (VRL-SGD-W): the caller sizes the first round k=1
      (``launch/train.py`` does).  Stagewise schedules
      (``vrl_cfg.comm_schedule``): the caller sizes each round from the
      schedule's stage and wraps round_step in ``engine.RoundCache`` so a
      run compiles one executable per distinct k; per-step ``train_step``
      reads the same schedule through ``engine.should_sync``, so the two
      drivers sync at identical steps.

Worker parallelism is a ``vmap`` over the leading worker axis; on the
production mesh that axis is sharded over the worker mesh axes so local steps
compile with no cross-worker collectives, which is exactly the property the
paper's communication complexity counts.

Hierarchical (``vrl_cfg.algorithm == "hier_vrl_sgd"``): the worker
population is the pod-major (P, D) grid of ``vrl_cfg.hier`` and the vmap is
doubled over it — tokens still arrive worker-stacked (W, ...) and are folded
to (P, D, ...) here.  ``sync1_step``/``sync2_step`` expose the per-level
syncs (intra-pod / cross-pod) for the dry-run's per-axis collective-bytes
artifacts.

Backend selection: ``vrl_cfg.update_backend`` (resolved by
``core.engine.resolve_backend``).

  "reference" — tree-structured WorkerState, per-leaf jax.tree.map update.
  "fused"     — flat-buffer engine (core/engine.py): state is a
                FlatWorkerState of contiguous (W, R, C) buffers, the update
                math runs as fused Pallas kernels (one HBM pass per local
                step), and with ``mesh=`` given the sync lowers to a single
                all-reduce of the flat buffer via shard_map.  The model
                forward still sees a normal pytree (engine.params_tree).
  "xla"       — the same flat-buffer engine with the update math as plain
                jnp (kernels/xla_update): XLA fuses the elementwise chain,
                so this is the fast executor where Pallas would interpret.
  "auto"      — fused on TPU/GPU, xla elsewhere (the default).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, VRLConfig
from repro.core import engine as engine_mod
from repro.core import get_algorithm
from repro.core.types import MemberState
from repro.models import transformer
from repro.train.loss import chunked_cross_entropy_lm, cross_entropy_lm


def clip_by_global_norm(grads, max_norm: float):
    """Per-worker global-norm clipping (standard training substrate)."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads)


class StepBundle(NamedTuple):
    init_state: callable
    train_step: callable
    local_step: callable
    sync_step: callable
    grads_fn: callable
    average_model: Any = None   # (state,) -> single-model pytree
    engine: Any = None          # core.engine.Engine on the engine backends
    sync1_step: Any = None      # hierarchical only: intra-pod sync alone
    sync2_step: Any = None      # hierarchical only: cross-pod sync alone
    round_step: Any = None      # (state, tokens_k, labels_k) ->
                                #   (state, (k,) losses): one scanned round
    round_step_fault: Any = None  # (state, tokens_k, labels_k, gmul) ->
                                #   (state, losses): round_step with a
                                #   (k, W) per-step/worker gradient
                                #   multiplier (1 = clean; NaN/Inf/scale
                                #   injects a fault on that worker) —
                                #   the chaos harness's entry point
    health: Any = None          # (state, loss) -> () bool: loss finite
                                #   AND every ACTIVE worker's params
                                #   finite (dead rows excluded) — the
                                #   divergence guard's predicate


def make_train_step(model_cfg: ModelConfig, vrl_cfg: VRLConfig,
                    *, remat: bool = True, unroll: int = 1,
                    param_dtype=jnp.float32,
                    chunked_ce: int = 0, mesh=None,
                    worker_axes=("data",)) -> StepBundle:
    """``chunked_ce > 0`` streams the LM loss over vocab chunks of that
    size — the (B, S, V) logits tensor is never materialized (a ~10x-S
    fp32 buffer at 256k vocab).  ``mesh``/``worker_axes`` only affect the
    fused backend (shard_map worker axis for the flat all-reduce)."""
    alg = get_algorithm(vrl_cfg.algorithm)
    # XLA cannot partition a Pallas kernel: where the model runs
    # partitioned over a mesh of several devices, explicit positions keep
    # attention on its dense core (attention.attend_full)
    partitioned = mesh is not None and mesh.size > 1

    @jax.named_scope("model")
    def loss_fn(params, tokens, labels):
        positions = (jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                      tokens.shape[:2])
                     if partitioned else None)
        if chunked_ce:
            hidden, aux = transformer.forward(model_cfg, params, tokens,
                                              positions=positions,
                                              remat=remat, unroll=unroll,
                                              return_hidden=True)
            head = (params["embed"] if model_cfg.tie_embeddings
                    else params["lm_head"])
            loss = chunked_cross_entropy_lm(
                hidden, head, labels, chunk=chunked_ce,
                head_is_embed=model_cfg.tie_embeddings)
        else:
            logits, aux = transformer.forward(model_cfg, params, tokens,
                                              positions=positions,
                                              remat=remat, unroll=unroll)
            loss = cross_entropy_lm(logits, labels)
        if model_cfg.num_experts:
            loss = loss + model_cfg.router_aux_loss * aux
        return loss

    def per_worker(params, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
        if vrl_cfg.clip_norm:
            grads = clip_by_global_norm(grads, vrl_cfg.clip_norm)
        return grads, loss

    hier = engine_mod.get_spec(vrl_cfg.algorithm).sync == "vrl2"
    if hier:
        hcfg = engine_mod.hier_config(vrl_cfg)

        def stack_vmap(params, tokens, labels):
            """Pod-major grid: tokens arrive worker-stacked (W, b, s) and
            fold to (P, D, b, s); grads/losses carry (P, D) leading axes."""
            tok = tokens.reshape(hcfg.grid + tokens.shape[1:])
            lab = labels.reshape(hcfg.grid + labels.shape[1:])
            return jax.vmap(jax.vmap(per_worker))(params, tok, lab)
    else:
        def stack_vmap(params, tokens, labels):
            return jax.vmap(per_worker)(params, tokens, labels)

    def _make_round(grads_fn, local_fn, round_end_fn):
        """Round factory shared by all backends: scan k (tokens, labels)
        pairs through local steps, close with the round-ending sync, and
        return the per-step losses as a (k,) device array."""

        def round_step(state, tokens_k, labels_k):
            def body(s, tl):
                grads, loss = grads_fn(s, tl[0], tl[1])
                return local_fn(s, grads), loss

            state, losses = jax.lax.scan(body, state,
                                         (tokens_k, labels_k))
            return round_end_fn(state), losses

        return round_step

    def _grad_mul(grads, m):
        """Scale a worker-stacked grad pytree by a per-worker multiplier
        ``m`` (W,) — folded to the (P, D) grid on the hierarchical path.
        1.0 is a no-op; NaN/Inf poisons that worker's local step exactly
        like a sick accelerator would (clipping already happened, so the
        poison is not renormalized away)."""
        if hier:
            mg = m.reshape(hcfg.grid)
            return jax.tree.map(
                lambda g: g * mg.reshape(mg.shape + (1,) * (g.ndim - 2)
                                         ).astype(g.dtype), grads)
        return jax.tree.map(
            lambda g: g * m.reshape((-1,) + (1,) * (g.ndim - 1)
                                    ).astype(g.dtype), grads)

    def _make_round_fault(grads_fn, local_fn, round_end_fn):
        """Fault-injecting twin of ``_make_round``: the extra ``gmul``
        (k, W) array rides the same scan, so a chaos round compiles to
        the same one-sync program with one fused multiply added."""

        def round_step_fault(state, tokens_k, labels_k, gmul):
            def body(s, tl):
                grads, loss = grads_fn(s, tl[0], tl[1])
                return local_fn(s, _grad_mul(grads, tl[2])), loss

            state, losses = jax.lax.scan(
                body, state, (tokens_k, labels_k, gmul))
            return round_end_fn(state), losses

        return round_step_fault

    backend = engine_mod.resolve_backend(vrl_cfg)
    if backend == "reference" and getattr(vrl_cfg, "membership", False):
        raise ValueError(
            "membership (elastic fault tolerance) needs the flat-buffer "
            "engine's MemberState; update_backend='reference' has none — "
            "use 'auto', 'xla' or 'fused'")
    if backend == "reference" and vrl_cfg.overlap:
        raise ValueError(
            "overlap needs the flat-buffer engine (its double-buffered "
            "pend state); update_backend='reference' has no overlapped "
            "round — use 'auto', 'xla' or 'fused'")
    if backend != "reference":
        template = jax.eval_shape(functools.partial(
            transformer.init_params, model_cfg, dtype=param_dtype),
            jax.random.PRNGKey(0))
        eng = engine_mod.make_engine(vrl_cfg, template, mesh=mesh,
                                     worker_axes=tuple(worker_axes))

        def _loss_mean(state, losses):
            """Mean over ACTIVE workers when elastic membership is on —
            a dead worker's NaN loss must not poison the reported loss
            (or the divergence guard reading it).  Reciprocal-multiply so
            the full-mask program is bitwise ``jnp.mean``."""
            m = getattr(state, "member", ())
            if isinstance(m, MemberState):
                lm = m.active.reshape(losses.shape)
                n = (m.n_active if isinstance(m.n_pod, tuple)
                     else jnp.sum(m.n_pod))
                s = jnp.sum(jnp.where(lm > 0, losses, 0))
                return s * (1.0 / jnp.maximum(n, 1.0))
            return jnp.mean(losses)

        def grads_fn(state, tokens, labels):
            ptree = eng.params_tree(state)
            grads, losses = stack_vmap(ptree, tokens, labels)
            return grads, _loss_mean(state, losses)

        def health(state, loss):
            """() bool: loss finite and every ACTIVE worker's params
            finite.  Dead rows are excluded so a crashed worker's NaNs
            do not trip the guard after its drop."""
            p = state.params
            m = getattr(state, "member", ())
            if isinstance(m, MemberState):
                p = jnp.where(m.active > 0, p, 0)
            return jnp.isfinite(loss) & jnp.all(jnp.isfinite(p))

        def train_step(state, tokens, labels):
            grads, loss = grads_fn(state, tokens, labels)
            return eng.train_step(state, grads), loss

        def local_step(state, tokens, labels):
            grads, loss = grads_fn(state, tokens, labels)
            return eng.local_step(state, grads), loss

        def init_state(key, num_workers: int):
            params = transformer.init_params(model_cfg, key,
                                             dtype=param_dtype)
            return eng.init(params, num_workers)

        if eng.round_begin is not None:
            # overlapped round: issue the sync collective FIRST (over the
            # previous boundary's transmitted positions — no dependency on
            # this round's steps), scan the k local steps, fold the stale
            # result at the end.  Same signature as the blocking round, so
            # RoundCache/benches/drivers are agnostic.
            def round_step(state, tokens_k, labels_k):
                k = jax.tree.leaves(tokens_k)[0].shape[0]
                xbar = eng.round_begin(state, k)

                def body(s, tl):
                    grads, loss = grads_fn(s, tl[0], tl[1])
                    return eng.local_step(s, grads), loss

                state, losses = jax.lax.scan(body, state,
                                             (tokens_k, labels_k))
                return eng.round_fold(state, xbar), losses

            def round_step_fault(state, tokens_k, labels_k, gmul):
                k = jax.tree.leaves(tokens_k)[0].shape[0]
                xbar = eng.round_begin(state, k)

                def body(s, tl):
                    grads, loss = grads_fn(s, tl[0], tl[1])
                    return eng.local_step(s, _grad_mul(grads, tl[2])), loss

                state, losses = jax.lax.scan(
                    body, state, (tokens_k, labels_k, gmul))
                return eng.round_fold(state, xbar), losses
        else:
            round_step = _make_round(grads_fn,
                                     lambda s, g: eng.local_step(s, g),
                                     eng.round_end)
            round_step_fault = _make_round_fault(
                grads_fn, lambda s, g: eng.local_step(s, g), eng.round_end)
        return StepBundle(init_state, train_step, local_step, eng.sync,
                          grads_fn, eng.average_model, eng,
                          sync1_step=eng.sync1, sync2_step=eng.sync2,
                          round_step=round_step,
                          round_step_fault=round_step_fault,
                          health=health)

    def grads_fn(state, tokens, labels):
        grads, losses = stack_vmap(state.params, tokens, labels)
        return grads, jnp.mean(losses)

    def train_step(state, tokens, labels):
        grads, loss = grads_fn(state, tokens, labels)
        return alg.train_step(vrl_cfg, state, grads), loss

    def local_step(state, tokens, labels):
        grads, loss = grads_fn(state, tokens, labels)
        return alg.local_step(vrl_cfg, state, grads), loss

    def sync_step(state):
        return alg.sync(vrl_cfg, state)

    def init_state(key, num_workers: int):
        params = transformer.init_params(model_cfg, key, dtype=param_dtype)
        return alg.init(vrl_cfg, params, num_workers)

    sync1 = sync2 = None
    if hier:
        from repro.core import hierarchical as H
        sync1 = lambda s: H.sync_level1(vrl_cfg, s)       # noqa: E731
        sync2 = lambda s: H.sync_level2(vrl_cfg, s)       # noqa: E731

        def round_end(state):
            if hcfg.k2 % hcfg.k1:
                raise ValueError(
                    f"round execution needs k2 % k1 == 0; got "
                    f"k1={hcfg.k1}, k2={hcfg.k2}")
            state = H.sync_level1(vrl_cfg, state)
            do2 = (state.step - state.last_sync2) >= hcfg.k2
            return jax.lax.cond(
                do2, lambda s: H.sync_level2(vrl_cfg, s),
                lambda s: s, state)
    else:
        round_end = sync_step

    def health(state, loss):
        ok = jnp.isfinite(loss)
        for leaf in jax.tree.leaves(state.params):
            ok = ok & jnp.all(jnp.isfinite(leaf))
        return ok

    round_step = _make_round(grads_fn,
                             lambda s, g: alg.local_step(vrl_cfg, s, g),
                             round_end)
    round_step_fault = _make_round_fault(
        grads_fn, lambda s, g: alg.local_step(vrl_cfg, s, g), round_end)
    return StepBundle(init_state, train_step, local_step, sync_step,
                      grads_fn, alg.average_model,
                      sync1_step=sync1, sync2_step=sync2,
                      round_step=round_step,
                      round_step_fault=round_step_fault,
                      health=health)
