"""Flat-buffer algorithm engine — the shared execution core for VRL-SGD and
its baselines.

Engine architecture
===================

Every algorithm in ``repro.core`` is the same loop over worker-stacked,
model-sized state: a *local* elementwise update per step (eqs. 5-6: the
inner-optimizer step on the Δ-corrected gradient) and a periodic *sync*
(eq. 4: model averaging — the one communication event the paper's
O(T^{1/2}N^{3/2}) complexity counts — plus the Δ update).  The engine
factors that loop into two orthogonal pieces:

  1. ``AlgoSpec`` — a thin *description* of an algorithm: does the local
     step subtract Δ (and BVR-L-SGD's bias variate B), is the gradient
     all-reduced every step (S-SGD), and which sync rule runs at period
     boundaries ("vrl" | "average" | "elastic" | "none" | "bvr").
     ``core/{vrl_sgd,local_sgd,ssgd,easgd,stl_sgd,bvr_l_sgd}.py`` are now
     just named specs plus thin wrappers over this module.

  2. Two interchangeable executors over a spec:

     * the **reference** executor (``ref_*``): per-leaf ``jax.tree.map``
       math over the parameter pytree — easy to read, slow (5+ HBM passes
       per local step), and the oracle the fused path is tested against.

     * the **fused** executor (``make_engine``): parameters, Δ, and the
       inner-optimizer moments are flattened ONCE at init into contiguous
       per-worker (W, R, C) buffers (layout + unravel spec: ``core/flat``),
       and every step runs a single fused Pallas kernel
       (``kernels/vrl_update.fused_*``) — one HBM pass for the local step,
       one fused pass + a SINGLE flat all-reduce for sync.

Worker axis
-----------

With ``mesh=None`` (CPU / single device) the worker axis is just the
leading buffer dimension and "all-reduce" is ``jnp.mean`` over it.  Given a
mesh, the engine step functions are wrapped in ``shard_map`` over the
configured worker axes: the sync's model average lowers to exactly one
``psum`` over the flat (R, C) buffer — the compiled HLO contains one
all-reduce per sync and none in local steps (asserted in
``tests/test_engine_collectives.py``).

Two-level hierarchy
-------------------

``hier_vrl_sgd`` (sync rule "vrl2", ``configs.base.HierConfig``) runs the
same loop over a pod-major (P, D, R, C) worker grid with one correction per
link tier: Δ1 per worker (intra-pod, period k1) and Δ2 per pod carried as a
(P, 1, R, C) buffer (cross-pod, period k2 ≥ k1).  On a mesh the level-1
sync lowers to one ``psum`` over the intra-pod axis and the level-2 sync to
one ``psum`` over the cross-pod axis (``HierConfig.axes``), so the slow DCI
tier is touched k2/k1 times less often than flat VRL-SGD at k1.  Both
executors cover it: the per-leaf reference path over ``types.HierState``
and the fused path over ``HierFlatState`` with the
``kernels/vrl_update.fused_hier_*`` / ``fused_sync_hier{1,2}`` kernels.

Backend selection
-----------------

``VRLConfig.update_backend`` ("auto" | "fused" | "xla" | "reference")
threads from ``configs/base.py`` through ``train/train_loop.py`` to the
launch drivers.  The flat-buffer engine has TWO interchangeable executors
over the same state layout:

  * "fused" — the Pallas kernels (``kernels/vrl_update``): explicit HBM
    passes, the right choice where Pallas compiles (TPU/GPU).  On other
    backends Pallas falls back to interpret mode (python per block) and is
    orders of magnitude slower than either alternative.
  * "xla" — the identical (W, R, C) elementwise math as plain jnp
    (``kernels/xla_update``): XLA fuses the chain into one pass, so it is
    the fast executor on CPU (and a portable fallback anywhere).

``resolve_backend`` maps "auto" to fused on TPU/GPU and xla elsewhere;
forcing "fused" where interpret mode would run emits a one-line warning.
Tiling knobs (``block``, ``lanes``, ``interpret``) live in
``configs.base.EngineConfig``.

Round execution
---------------

``Engine.round_step(state, grads_k)`` makes the *communication round* the
unit of compilation: k local steps run under one ``lax.scan`` over
pre-flattened (k, ...) gradient buffers — no per-step python dispatch, no
host sync — followed by ``round_end`` (flat: the sync; hierarchical: the
level-1 sync plus the level-2 sync whenever the k2 cadence is due, which
requires k2 % k1 == 0).  Jit it with ``donate_argnums=(0,)`` and the
compiled HLO aliases every state buffer in place (asserted in
``tests/test_round_scan.py``); on a mesh the whole round still lowers to
exactly one sync collective per k steps
(``tests/test_engine_collectives.py``).

Every local step runs under ``jax.named_scope("engine.local_update")`` and
every sync (flat, and each hierarchical level) under ``"engine.sync"``, so
the compiled ops carry those names in their HLO ``op_name`` and a profile
can split the round's device time by layer (``obs.scopemap``,
``tests/test_scopes.py``).

Rounds take k from the leading axis of the grads stack, so a stagewise
``CommSchedule`` (``core/schedule.py``, ``VRLConfig.comm_schedule``) just
feeds differently-sized stacks per stage: ``RoundCache`` keys one compiled
round executable per distinct k, so a whole stagewise run compiles at most
``len(stages)`` rounds, and the sync math stays exact at any period because
it uses the true elapsed k_eff.

Compressed sync (bytes-per-round)
---------------------------------

``VRLConfig.compress`` / ``compress2`` (``repro.comm.CompressorSpec``:
``none`` | ``int8`` per-row-scaled quantization | ``topk`` fixed-k
sparsification, optional error feedback) compress the payload of every
communication event: each worker transmits its DRIFT against a shared
reference (the value every participant holds after the previous sync,
carried in a ``CommState.ref`` buffer), the decompressed drifts are
averaged by the SAME single flat all-reduce, and the compression error is
carried per worker in a donated ``CommState.resid`` buffer (EF-SGD).
S-SGD, whose communication is the per-step gradient all-reduce, compresses
the gradient itself (ref ≡ 0).  The hierarchy compresses per level —
``compress`` drives the intra-pod sync1, ``compress2`` the slow cross-pod
sync2 (``HierCommState`` carries per-level ref/resid) — and ``none`` /
``topk`` at rate 1 resolve to the ORIGINAL code path, bitwise, with no
extra buffers.  Executors: Pallas ``kernels/vrl_update.fused_ef_*`` (one
HBM pass builds payload → decompressed + residual), jnp twins in
``kernels/xla_update``, and per-leaf ``repro.comm.compressors.ef_leaf`` on
the reference path.

Overlapped rounds (``VRLConfig.overlap``)
-----------------------------------------

The blocking round waits on the sync collective at every boundary.  With
``overlap=True`` the round driver instead issues THE sync all-reduce at
round START, over the positions every participant transmitted at the
PREVIOUS boundary (``types.OverlapState.pend``), so the collective's data
dependencies are all ready before the k-step ``lax.scan`` begins and the
scheduler can run wire and compute concurrently; the one-round-stale mean
is folded in at round end (``kernels/*.fused_fold_overlap*``):

  c_i = x̂_stale − pend_i;   p' = p + c_i;   Δ' = Δ + c_i/(pend_k_i·γ)

Σ_i c_i = 0, so the worker-mean trajectory is untouched and Σ_i Δ_i stays
0 — VRL-SGD's Δ is already a previous-round quantity, so the staleness
rides the existing math.  The compiled round still lowers to exactly one
sync all-reduce per k steps.  ``deadline`` adds straggler tolerance: each
round each participant misses its capture with that probability
(simulated), keeps its last transmitted position (absolute positions make
misses self-healing), and — under compressed sync — parks the missed
payload in its EF residual.  Hierarchical runs overlap the cross-pod
sync2 (the slow DCI tier) only; sync1 stays blocking.  ``overlap=False``
builds the exact blocking program (no new buffers or ops, bitwise).  Only
the round drivers (``round_step``/``round_begin``+``round_fold``)
overlap; the per-step ``train_step`` path stays blocking and should not
be mixed with overlapped rounds (it would not maintain ``pend``).

Elastic membership (``VRLConfig.membership``)
---------------------------------------------

Real workers crash and rejoin.  With ``membership=True`` the state carries
a ``types.MemberState`` (an active-worker {0,1} mask plus the active
counts) and every sync mean runs over the ACTIVE workers only: dead rows
are excluded with a ``where`` (a multiply would propagate a crashed
worker's NaNs as ``NaN * 0``) and the divisor is the state-carried count,
so the masked sync is STILL exactly one all-reduce per round — no second
collective to count survivors.  Dead rows stay allocated (layouts and
compiled programs never change); ``Engine.set_membership(state, active)``
is the out-of-round repair step that makes a membership change safe:

  * continuing workers: Δ (and BVR's B) recentred to mean zero over the
    continuing set — algebraically identical to redistributing every
    dropped worker's Δ across the survivors, but computed without reading
    a dropped row, so crash NaNs cannot leak — keeping Σ_i Δ_i = 0 exact;
  * dropped + rejoining workers: params (and overlap ``pend``) re-seeded
    from the continuing consensus x̂, Δ/B/moments/EF residuals zeroed — a
    rejoiner restarts from the current reference point.

With the mask fully active the trajectory is bitwise the
``membership=False`` path.  Hierarchical runs mask per level: intra-pod
means divide by per-pod active counts and the cross-pod mean is uniform
over ALIVE pods (the weighting that keeps Σ_p Δ2 = 0 through pod churn).
easgd's center update assumes a fixed worker count and refuses the mask.
"""
from __future__ import annotations

import functools
import math
import warnings
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import compressors as comm_mod
from repro.configs.base import HierConfig, VRLConfig
from repro.core import flat
from repro.core import schedule as schedule_mod
from repro.core.types import (CommState, HierCommState, HierState,
                              MemberState, OverlapState, WorkerState)
from repro.kernels import vrl_update as vu
from repro.kernels import xla_update as xu
from repro.obs import scopemap
from repro.optim.optimizers import AdamState, SM3Pair, make_inner


BACKENDS = ("auto", "fused", "xla", "reference")


def resolve_backend(cfg_or_name) -> str:
    """Resolve ``update_backend`` to a concrete executor name.

    "auto" picks the Pallas kernels where they compile (TPU/GPU) and the
    XLA executor elsewhere (CPU) — never the interpret-mode fallback.
    Accepts a VRLConfig or a bare string.
    """
    name = getattr(cfg_or_name, "update_backend", cfg_or_name)
    if name not in BACKENDS:
        raise ValueError(f"unknown update_backend {name!r}; known: "
                         f"{BACKENDS}")
    if name == "auto":
        return "fused" if jax.default_backend() in ("tpu", "gpu") else "xla"
    return name


# ===================================================================== specs
class AlgoSpec(NamedTuple):
    """An algorithm as a description over the shared engine.

    ``sync`` names the rule that runs at period boundaries; "vrl2" is the
    two-level rule (intra-pod "vrl" at k1, cross-pod "vrl" at k2) whose
    state lives on a pod-major worker grid instead of a flat worker axis;
    "bvr" is the VRL rule plus BVR-L-SGD's bias-variate EMA.
    """

    name: str
    use_delta: bool        # local step applies v = g − Δ (eq. 6)
    grad_all_reduce: bool  # S-SGD: mean gradients over workers every step
    sync: str              # "vrl" | "average" | "elastic" | "none" | "vrl2"
                           # | "bvr"
    has_center: bool       # EASGD center variable x̃
    warmup_aware: bool     # honors VRLConfig.warmup (first period k=1)
    use_bias: bool = False  # BVR-L-SGD: local step also subtracts B
    stagewise: bool = False  # STL-SGD: default to a stagewise CommSchedule


ALGO_SPECS = {
    "vrl_sgd": AlgoSpec("vrl_sgd", use_delta=True, grad_all_reduce=False,
                        sync="vrl", has_center=False, warmup_aware=True),
    "local_sgd": AlgoSpec("local_sgd", use_delta=False, grad_all_reduce=False,
                          sync="average", has_center=False,
                          warmup_aware=False),
    "ssgd": AlgoSpec("ssgd", use_delta=False, grad_all_reduce=True,
                     sync="none", has_center=False, warmup_aware=False),
    "easgd": AlgoSpec("easgd", use_delta=False, grad_all_reduce=False,
                      sync="elastic", has_center=True, warmup_aware=False),
    "hier_vrl_sgd": AlgoSpec("hier_vrl_sgd", use_delta=True,
                             grad_all_reduce=False, sync="vrl2",
                             has_center=False, warmup_aware=False),
    # STL-SGD (Shen et al., 2020): Local SGD whose communication period
    # grows stagewise — the update structure IS local_sgd's; the stagewise
    # cadence comes from the CommSchedule (comm_schedule() below), so with
    # a constant schedule the trajectory is bitwise local_sgd.
    "stl_sgd": AlgoSpec("stl_sgd", use_delta=False, grad_all_reduce=False,
                        sync="average", has_center=False,
                        warmup_aware=False, stagewise=True),
    # BVR-L-SGD (Murata & Suzuki, 2021): VRL-SGD plus a bias-corrected
    # control variate.  The engine sees one gradient per step, so the
    # paper's same-sample anchor-gradient correction is carried in its
    # parameter-motion form: B_i is an EMA (rate cfg.bvr_beta) of the
    # per-round realized drift u_i = (x̂ − x_i)/(k_eff γ), subtracted in
    # every local step alongside Δ_i.  Σ_i B_i = 0 after every sync (same
    # argument as Δ), and bvr_beta=0 disables the correction at trace time
    # — the trajectory is then bitwise vrl_sgd.
    "bvr_l_sgd": AlgoSpec("bvr_l_sgd", use_delta=True,
                          grad_all_reduce=False, sync="bvr",
                          has_center=False, warmup_aware=True,
                          use_bias=True),
}


def flat_algorithms() -> Tuple[str, ...]:
    """Registry-derived names of the flat (non-hierarchical) algorithms —
    tests iterate this so new specs are covered automatically."""
    return tuple(n for n, s in sorted(ALGO_SPECS.items())
                 if s.sync != "vrl2")


def comm_schedule(cfg: VRLConfig):
    """The round schedule driving this config's sync cadence.

    ``cfg.comm_schedule`` when set; stl_sgd defaults to the STL-SGD
    stagewise-doubling ramp 1 → ``comm_period``; None otherwise (the
    constant ``comm_period`` cadence, the seed behaviour).  A schedule
    supersedes ``warmup`` — express a warm start as an initial k=1 stage.
    """
    if cfg.comm_schedule is not None:
        return cfg.comm_schedule
    if get_spec(cfg.algorithm).stagewise:
        return schedule_mod.stagewise_doubling(k0=1, k_max=cfg.comm_period)
    return None


def use_bias(spec: AlgoSpec, cfg: VRLConfig) -> bool:
    """True when the BVR bias variate is active.  ``bvr_beta == 0`` turns
    the whole B machinery off at trace time, so the compiled program (and
    trajectory) is bitwise the underlying VRL-SGD."""
    return spec.use_bias and bool(cfg.bvr_beta)


def hier_config(cfg: VRLConfig) -> HierConfig:
    """The two-level periods/grid; defaults to the flat period at k1=k2."""
    if cfg.hier is not None:
        return cfg.hier
    return HierConfig(k1=cfg.comm_period, k2=cfg.comm_period)


def get_spec(name: str) -> AlgoSpec:
    if name not in ALGO_SPECS:
        raise KeyError(f"unknown algorithm {name!r}; known: "
                       f"{sorted(ALGO_SPECS)}")
    return ALGO_SPECS[name]


def should_sync(spec: AlgoSpec, cfg: VRLConfig, step: jax.Array,
                last_sync: jax.Array) -> jax.Array:
    """True when ``step`` (post-increment) completes a communication period.

    With a ``CommSchedule`` the period is the schedule's for the round
    starting at ``last_sync`` (stage boundaries are compile-time constants,
    so this stays one jit); otherwise the constant ``comm_period``.
    VRL-SGD-W (Remark 5.3): with ``warmup`` the first period runs k=1.
    """
    sched = comm_schedule(cfg)
    if sched is not None:
        k = sched.period_starting_at(last_sync)
    elif spec.warmup_aware:
        k = jnp.where(cfg.warmup & (last_sync == 0) & (step <= 1),
                      1, cfg.comm_period)
    else:
        k = cfg.comm_period
    return (step - last_sync) >= k


# ======================================================== reference executor
# Per-leaf tree math — the oracle path.  Exactly the seed implementations,
# now generic over AlgoSpec.

def _bcast(tree, w: int):
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (w, *x.shape)).copy(),
                        tree)


def worker_mean(tree):
    return jax.tree.map(lambda x: jnp.mean(x, axis=0, keepdims=True), tree)


def average_model(state) -> Any:
    """x̂ — the evaluation model (paper reports metrics on the average)."""
    return jax.tree.map(lambda x: jnp.mean(x, axis=0), state.params)


def _ref_payload(tree_x, ref, resid):
    """Per-leaf compression payload: x − ref + resid in fp32 (``ref`` /
    ``resid`` trees optional; ref leaves broadcast against the worker
    axes)."""
    def one(x, *rest):
        p = x.astype(jnp.float32)
        i = 0
        if ref is not None:
            p = p - rest[i]
            i += 1
        if resid is not None:
            p = p + rest[i]
        return p

    extra = ([ref] if ref is not None else []) \
        + ([resid] if resid is not None else [])
    return jax.tree.map(one, tree_x, *extra)


def _leaf_rt(comp, payload_tree, n_lead: int):
    """Per-leaf EF round-trip over a payload tree → (dec tree, resid
    tree), tracing ``ef_leaf`` once per leaf."""
    outer = jax.tree.structure(payload_tree)
    pairs = jax.tree.map(
        lambda x: comm_mod.ef_leaf(comp, x, n_lead), payload_tree)
    return jax.tree_util.tree_transpose(
        outer, jax.tree.structure((0, 0)), pairs)


def ref_init(spec: AlgoSpec, cfg: VRLConfig, params: Any,
             num_workers: int) -> WorkerState:
    stacked = _bcast(params, num_workers)
    delta_dt = jnp.dtype(cfg.delta_dtype)
    delta = jax.tree.map(lambda x: jnp.zeros_like(x, dtype=delta_dt), stacked)
    inner = make_inner(cfg).init(stacked)
    center = (jax.tree.map(lambda x: x[0].astype(jnp.float32), stacked)
              if spec.has_center else None)
    bias = (jax.tree.map(lambda x: jnp.zeros_like(x, dtype=delta_dt),
                         stacked) if use_bias(spec, cfg) else None)
    comp, _ = comm_mod.resolve_pair(cfg)
    comm = ()
    if comp is not None:
        # residuals in fp32 so the EF invariant (resid + dec == payload)
        # is exact; ref is the shared post-sync value (init: the broadcast
        # params themselves) — () for S-SGD's gradient compression
        resid = (jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                              stacked) if comp.error_feedback else ())
        ref = (() if (spec.grad_all_reduce or spec.sync == "none")
               else jax.tree.map(lambda x: x.astype(jnp.float32), params))
        comm = CommState(resid=resid, ref=ref)
    return WorkerState(params=stacked, delta=delta, inner=inner,
                       center=center, step=jnp.zeros((), jnp.int32),
                       last_sync=jnp.zeros((), jnp.int32), bias=bias,
                       comm=comm)


def corrected_grads(state: WorkerState, grads: Any) -> Any:
    """v_i = g_i − Δ_i  (eq. 6)."""
    return jax.tree.map(lambda g, d: g - d.astype(g.dtype), grads,
                        state.delta)


def ref_local_step(spec: AlgoSpec, cfg: VRLConfig, state: WorkerState,
                   grads: Any) -> WorkerState:
    opt = make_inner(cfg)
    if spec.grad_all_reduce:
        # S-SGD's "local" step IS a train step: that's the point of the paper.
        comp, _ = comm_mod.resolve_pair(cfg)
        new_comm = state.comm
        if comp is not None:
            # the gradient IS the communicated payload: compress it (ref≡0)
            e = state.comm.resid if comp.error_feedback else None
            dec, res = _leaf_rt(comp, _ref_payload(grads, None, e), 1)
            gbar = jax.tree.map(
                lambda d: jnp.mean(d, axis=0, keepdims=True), dec)
            if comp.error_feedback:
                new_comm = state.comm._replace(resid=res)
        else:
            gbar = jax.tree.map(lambda g: jnp.mean(g, axis=0, keepdims=True),
                                grads)
        gbar = jax.tree.map(lambda g, x: jnp.broadcast_to(g, x.shape),
                            gbar, state.params)
        new_params, new_inner = opt.update(state.params, gbar, state.inner)
        return state._replace(params=new_params, inner=new_inner,
                              step=state.step + 1, last_sync=state.step + 1,
                              comm=new_comm)
    v = corrected_grads(state, grads) if spec.use_delta else grads
    if use_bias(spec, cfg):
        v = jax.tree.map(lambda g, b: g - b.astype(g.dtype), v, state.bias)
    new_params, new_inner = opt.update(state.params, v, state.inner)
    return state._replace(params=new_params, inner=new_inner,
                          step=state.step + 1)


def ref_sync(spec: AlgoSpec, cfg: VRLConfig, state: WorkerState
             ) -> WorkerState:
    if spec.sync == "none":
        return state._replace(last_sync=state.step)

    # compressed sync: transmit per-worker drift against the shared ref,
    # average the decompressed drifts (mean_i x_i = ref + mean_i(x_i − ref))
    comp, _ = comm_mod.resolve_pair(cfg)
    new_comm = state.comm
    xbar = None
    if comp is not None:
        e = state.comm.resid if comp.error_feedback else None
        payload = _ref_payload(state.params, state.comm.ref, e)
        dec, res = _leaf_rt(comp, payload, 1)
        ref_new = jax.tree.map(lambda r, d: r + jnp.mean(d, axis=0),
                               state.comm.ref, dec)
        xbar = jax.tree.map(lambda x: x[None], ref_new)
        new_comm = CommState(resid=(res if comp.error_feedback else ()),
                             ref=ref_new)

    if spec.sync == "elastic":
        # Zhang et al. parameterize elasticity as beta/N (beta = easgd_alpha).
        n = jax.tree.leaves(state.params)[0].shape[0]
        a = cfg.easgd_alpha / n

        def upd_worker(x, c):
            return (x.astype(jnp.float32)
                    - a * (x.astype(jnp.float32) - c)).astype(x.dtype)

        if xbar is None:
            def upd_center(c, x):
                xb = jnp.mean(x.astype(jnp.float32), axis=0)
                return (1.0 - n * a) * c + n * a * xb

            new_center = jax.tree.map(upd_center, state.center, state.params)
        else:
            new_center = jax.tree.map(
                lambda c, xb: (1.0 - n * a) * c + n * a * xb[0],
                state.center, xbar)
        new_params = jax.tree.map(upd_worker, state.params, state.center)
        return state._replace(params=new_params, center=new_center,
                              last_sync=state.step, comm=new_comm)

    if xbar is None:
        xbar = worker_mean(state.params)                # the all-reduce
    new_params = jax.tree.map(
        lambda x, xb: jnp.broadcast_to(xb, x.shape).astype(x.dtype),
        state.params, xbar)
    if spec.sync == "average":
        return state._replace(params=new_params, last_sync=state.step,
                              comm=new_comm)

    # "vrl"/"bvr": Δ_i ← Δ_i + u_i, u_i = (x̂ − x_i)/(k_eff γ)  (eq. 4)
    k_eff = jnp.maximum(state.step - state.last_sync, 1).astype(jnp.float32)

    def drift(x, xb):
        return ((xb.astype(jnp.float32) - x.astype(jnp.float32))
                / (k_eff * cfg.learning_rate))

    def upd_delta(d, x, xb):
        return (d.astype(jnp.float32) + drift(x, xb)).astype(d.dtype)

    new_delta = jax.tree.map(upd_delta, state.delta, state.params, xbar)
    new_bias = state.bias
    if spec.sync == "bvr" and use_bias(spec, cfg):
        # B_i ← (1−β)·B_i + β·u_i — the bias-variate EMA of realized drift
        beta = cfg.bvr_beta

        def upd_bias(b, x, xb):
            return ((1.0 - beta) * b.astype(jnp.float32)
                    + beta * drift(x, xb)).astype(b.dtype)

        new_bias = jax.tree.map(upd_bias, state.bias, state.params, xbar)
    return state._replace(params=new_params, delta=new_delta,
                          bias=new_bias, last_sync=state.step,
                          comm=new_comm)


def ref_train_step(spec: AlgoSpec, cfg: VRLConfig, state: WorkerState,
                   grads: Any) -> WorkerState:
    state = ref_local_step(spec, cfg, state, grads)
    if spec.sync == "none":
        return state
    return jax.lax.cond(
        should_sync(spec, cfg, state.step, state.last_sync),
        lambda s: ref_sync(spec, cfg, s), lambda s: s, state)


# ---------------------------------------------- reference executor ("vrl2")
# The two-level rule over a pod-major (P, D, ...) tree state — the oracle
# for the fused hierarchical path (``core/hierarchical.py`` is a thin
# wrapper over these).

def ref_hier_init(cfg: VRLConfig, params: Any,
                  grid: Tuple[int, int]) -> HierState:
    p, d = grid
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (p, d, *x.shape)).copy(), params)
    dt = jnp.dtype(cfg.delta_dtype)
    z = lambda x: jnp.zeros_like(x, dtype=dt)
    d2 = jax.tree.map(lambda x: jnp.zeros((p, 1, *x.shape[2:]), dt), stacked)
    inner = make_inner(cfg).init(stacked)
    comp1, comp2 = comm_mod.resolve_pair(cfg)
    comm = ()
    if comp1 is not None or comp2 is not None:
        f32z = lambda t: jax.tree.map(
            lambda x: jnp.zeros_like(x, jnp.float32), t)
        comm = HierCommState(
            resid1=(f32z(stacked) if comp1 and comp1.error_feedback
                    else ()),
            ref1=(jax.tree.map(lambda x: jnp.broadcast_to(
                x.astype(jnp.float32), (p, 1, *x.shape)).copy(), params)
                if comp1 else ()),
            resid2=(jax.tree.map(lambda x: jnp.zeros(
                (p, 1, *x.shape), jnp.float32), params)
                if comp2 and comp2.error_feedback else ()),
            ref2=(jax.tree.map(lambda x: x.astype(jnp.float32), params)
                  if comp2 else ()))
    return HierState(params=stacked, delta1=jax.tree.map(z, stacked),
                     delta2=d2, inner=inner,
                     step=jnp.zeros((), jnp.int32),
                     last_sync1=jnp.zeros((), jnp.int32),
                     last_sync2=jnp.zeros((), jnp.int32), comm=comm)


def ref_hier_local_step(cfg: VRLConfig, state: HierState,
                        grads: Any) -> HierState:
    """x ← inner_opt(x, g − Δ1 − Δ2): zero cross-worker communication."""
    v = jax.tree.map(
        lambda g, d1, d2: g - d1.astype(g.dtype) - d2.astype(g.dtype),
        grads, state.delta1, state.delta2)
    new_params, new_inner = make_inner(cfg).update(state.params, v,
                                                  state.inner)
    return state._replace(params=new_params, inner=new_inner,
                          step=state.step + 1)


def ref_hier_sync1(cfg: VRLConfig, state: HierState) -> HierState:
    """Intra-pod sync: mean over axis 1 (the pod-internal worker axis)."""
    k_eff = jnp.maximum(state.step - state.last_sync1, 1).astype(jnp.float32)
    comp1, _ = comm_mod.resolve_pair(cfg)
    new_comm = state.comm
    if comp1 is not None:
        e = state.comm.resid1 if comp1.error_feedback else None
        payload = _ref_payload(state.params, state.comm.ref1, e)
        dec, res = _leaf_rt(comp1, payload, 2)
        xbar = jax.tree.map(
            lambda r, d: r + jnp.mean(d, axis=1, keepdims=True),
            state.comm.ref1, dec)
        new_comm = state.comm._replace(
            ref1=xbar, resid1=(res if comp1.error_feedback else ()))
    else:
        xbar = jax.tree.map(lambda x: jnp.mean(x, axis=1, keepdims=True),
                            state.params)

    def upd(d, x, xb):
        return (d.astype(jnp.float32)
                + (xb.astype(jnp.float32) - x.astype(jnp.float32))
                / (k_eff * cfg.learning_rate)).astype(d.dtype)

    new_d1 = jax.tree.map(upd, state.delta1, state.params, xbar)
    new_p = jax.tree.map(
        lambda x, xb: jnp.broadcast_to(xb, x.shape).astype(x.dtype),
        state.params, xbar)
    return state._replace(params=new_p, delta1=new_d1,
                          last_sync1=state.step, comm=new_comm)


def ref_hier_sync2(cfg: VRLConfig, state: HierState) -> HierState:
    """Cross-pod sync. Assumes a level-1 sync at the same step (so every
    worker already holds its pod average)."""
    k_eff = jnp.maximum(state.step - state.last_sync2, 1).astype(jnp.float32)
    comp1, comp2 = comm_mod.resolve_pair(cfg)
    new_comm = state.comm
    pod_avg = jax.tree.map(lambda x: jnp.mean(x, axis=1, keepdims=True),
                           state.params)
    if comp2 is not None:
        e = state.comm.resid2 if comp2.error_feedback else None
        payload = _ref_payload(pod_avg, state.comm.ref2, e)
        dec, res = _leaf_rt(comp2, payload, 2)
        glob_sm = jax.tree.map(lambda r, d: r + jnp.mean(d, axis=(0, 1)),
                               state.comm.ref2, dec)
        glob = jax.tree.map(lambda x: x[None, None], glob_sm)
        new_comm = new_comm._replace(
            ref2=glob_sm, resid2=(res if comp2.error_feedback else ()))
    else:
        glob = jax.tree.map(lambda x: jnp.mean(x, axis=(0, 1),
                                               keepdims=True), state.params)
    if comp1 is not None:
        # level-2 just moved every worker to x̂: re-anchor the level-1
        # drift reference so the next intra-pod payload is small again
        new_comm = new_comm._replace(ref1=jax.tree.map(
            lambda g, r1: jnp.broadcast_to(g.astype(jnp.float32), r1.shape),
            glob, new_comm.ref1))

    def upd(d2, pa, g):
        return (d2.astype(jnp.float32)
                + (g.astype(jnp.float32) - pa.astype(jnp.float32))
                / (k_eff * cfg.learning_rate)).astype(d2.dtype)

    new_d2 = jax.tree.map(upd, state.delta2, pod_avg, glob)
    new_p = jax.tree.map(
        lambda x, g: jnp.broadcast_to(g, x.shape).astype(x.dtype),
        state.params, glob)
    return state._replace(params=new_p, delta2=new_d2,
                          last_sync2=state.step, comm=new_comm)


def ref_hier_train_step(cfg: VRLConfig, state: HierState, grads: Any, *,
                        k1: Optional[int] = None,
                        k2: Optional[int] = None) -> HierState:
    hcfg = hier_config(cfg)
    k1 = hcfg.k1 if k1 is None else k1
    k2 = hcfg.k2 if k2 is None else k2
    state = ref_hier_local_step(cfg, state, grads)
    do1 = (state.step - state.last_sync1) >= k1
    do2 = (state.step - state.last_sync2) >= k2
    state = jax.lax.cond(do1 | do2, lambda s: ref_hier_sync1(cfg, s),
                         lambda s: s, state)
    return jax.lax.cond(do2, lambda s: ref_hier_sync2(cfg, s),
                        lambda s: s, state)


def hier_average_model(state: HierState) -> Any:
    """x̂ — the evaluation model, averaged over the whole (P, D) grid."""
    return jax.tree.map(lambda x: jnp.mean(x, axis=(0, 1)), state.params)


# ============================================================ fused executor
class FlatWorkerState(NamedTuple):
    """Worker-stacked algorithm state as contiguous flat buffers.

    ``params``/``delta``/moments: (W, R, C); ``center``: (R, C) fp32
    (EASGD only); Δ is () for algorithms that never use it, as is ``bias``
    (BVR-L-SGD's (W, R, C) variate B) for every other algorithm.  The
    unravel spec (``flat.FlatSpec``) lives on the Engine, not in the state
    — it is static layout, checkpointed as metadata
    (``checkpoint.save_flat_state``).
    """

    params: jax.Array
    delta: Any
    inner: Any
    center: Any
    step: jax.Array
    last_sync: jax.Array
    bias: Any = ()
    comm: Any = ()              # compressed-sync CommState: resid (W, R, C)
                                # fp32, ref (R, C) fp32 — () uncompressed
    overlap: Any = ()           # overlapped-round OverlapState: pend
                                # (W, R, C) fp32, pend_k (W, 1, 1) fp32 —
                                # () when cfg.overlap is off
    member: Any = ()            # elastic-membership MemberState: active
                                # (W, 1, 1) fp32 mask + n_active () fp32 —
                                # () when cfg.membership is off


class HierFlatState(NamedTuple):
    """Two-level algorithm state as pod-major contiguous flat buffers.

    ``params``/``delta1``/moments: (P, D, R, C); ``delta2``: (P, 1, R, C) —
    one shared cross-pod correction per pod, broadcast over the intra-pod
    axis by kernel index maps rather than materialized.  Invariants tested
    on this layout: Σ_d Δ1[p, d] = 0 within every pod after a level-1 sync,
    Σ_p Δ2[p] = 0 after a level-2 sync.
    """

    params: jax.Array
    delta1: jax.Array
    delta2: jax.Array
    inner: Any
    step: jax.Array
    last_sync1: jax.Array
    last_sync2: jax.Array
    comm: Any = ()              # per-level HierCommState: resid1
                                # (P, D, R, C), ref1 (P, 1, R, C), resid2
                                # (P, 1, R, C), ref2 (R, C) — () uncompressed
    overlap: Any = ()           # overlapped level-2 OverlapState: pend
                                # (P, 1, R, C) fp32, pend_k (P, 1, 1, 1)
                                # fp32 — () when cfg.overlap is off
    member: Any = ()            # elastic-membership MemberState: active
                                # (P, D, 1, 1) fp32, n_pod (P, 1, 1, 1)
                                # per-pod counts, n_active () = alive pods
                                # — () when cfg.membership is off


class Engine(NamedTuple):
    """Bound flat-buffer-executor closures for one (algorithm, model) pair."""

    algorithm: str
    spec: flat.FlatSpec
    algo: AlgoSpec
    init: Callable              # (params_tree, num_workers) -> state
    train_step: Callable        # (state, grads_tree) -> state
    local_step: Callable        # (state, grads_tree) -> state
    sync: Callable              # (state,) -> state (hier: level-1 + level-2)
    average_model: Callable     # (state,) -> single-model pytree
    params_tree: Callable       # (state,) -> worker-stacked params pytree
    sync1: Any = None           # hier only: intra-pod sync alone
    sync2: Any = None           # hier only: cross-pod sync alone
    grid: Any = None            # hier only: the (P, D) worker grid
    round_step: Any = None      # (state, grads_k) -> state: k scanned local
                                # steps + round_end, one compilation unit
    round_end: Any = None       # (state,) -> state: the round-closing sync
                                # (hier: sync1 + conditional k2-cadence sync2)
    round_step_flat: Any = None  # (state, gk_buf) -> state: round over a
                                 # pre-flattened (k, W/grid, R, C) buffer
    round_begin: Any = None     # overlap only: (state, k) -> x̂_stale, the
                                # round-START sync collective (flat engines
                                # ignore k; hier needs it for the k2
                                # cadence).  None when overlap is off —
                                # callers dispatch on that.
    round_fold: Any = None      # overlap only: (state, x̂_stale) -> state,
                                # the round-END stale fold (hier: blocking
                                # sync1 + conditional level-2 fold)
    backend: str = "fused"      # resolved executor: "fused" | "xla"
    interpret: bool = False     # the fused kernels run as interpreted
                                # Python (off-TPU/GPU), not compiled
    compressors: Any = (None, None)  # resolved (level-1, level-2)
                                     # CompressorSpecs (None = identity)
    set_membership: Any = None  # membership only: (state, (W,) mask) ->
                                # state — the invariant-preserving repair
                                # for a changed active set (jit it with
                                # donate_argnums=(0,); NOT part of the
                                # compiled round).  None when
                                # cfg.membership is off.
    recenter_drift: Any = None  # client sampling: (state,) -> state —
                                # re-zero Σ Δ (and Σ B) over the worker
                                # rows currently loaded in the buffers.  A
                                # sampled cohort's corrections sum to the
                                # cohort mean, not zero (Σ_i Δ_i = 0 holds
                                # over ALL M clients, not over W of them);
                                # run this after a cohort gather, BEFORE
                                # the round, whenever the cohort is a
                                # strict subset.  jit with
                                # donate_argnums=(0,); None on the
                                # hierarchical engine (client sampling is
                                # a flat-engine construct).
    diagnostics: Any = None     # observability: (state,) -> dict of
                                # algorithm-health scalars (drift
                                # dispersion, Δ-dispersion ζ² proxy,
                                # Σ Δ / Σ B invariant residuals, EF and
                                # moment norms, non-finite worker count).
                                # READ-ONLY — its own jit, never part of
                                # the compiled round, so the round's
                                # one-sync-all-reduce HLO contract is
                                # untouched; it may spend a few extra
                                # collectives, which is fine at
                                # --log-every cadence.  None on the
                                # reference backend.


class RoundCache:
    """Per-k cache of compiled round executables.

    A stagewise ``CommSchedule`` changes the round length k between stages.
    Each distinct k is a distinct input shape, so it is its own compilation
    of ``round_step`` — this cache keys one jitted executable per k (state
    donated), so a stagewise run compiles at most ``len(stages)`` round
    executables and every later round of the same k reuses its executable
    (asserted in ``tests/test_round_scan.py``).

    Works over any round callable whose extra operands carry k on their
    leading axis: ``Engine.round_step`` / ``round_step_flat`` (grads
    stacks) and ``StepBundle.round_step`` (token/label stacks).

    ``compiles`` counts actual traces (incremented at trace time), so a
    retrace of an existing k — which would break the "one executable per
    stage" contract — is visible too.  Each new executable is recorded in
    ``obs.scopemap``, which maps its ops to their named scopes.
    """

    def __init__(self, round_step: Callable, *, donate: bool = True):
        self._round = round_step
        self._donate = (0,) if donate else ()
        self._jits: dict = {}
        self.compiles = 0

    @staticmethod
    def round_k(*stacks) -> int:
        return int(jax.tree.leaves(stacks[0])[0].shape[0])

    def __call__(self, state, *stacks):
        k = self.round_k(*stacks)
        fn = self._jits.get(k)
        if fn is None:
            def traced(s, *rest):
                self.compiles += 1      # runs at trace time only
                return self._round(s, *rest)

            fn = jax.jit(traced, donate_argnums=self._donate)
            # Compiled ahead of the call below, which reuses the
            # executable; its HLO names each op's scope (obs.scopemap).
            scopemap.record(fn.lower(state, *stacks).compile())
            self._jits[k] = fn
        return fn(state, *stacks)

    @property
    def cached_ks(self) -> Tuple[int, ...]:
        return tuple(sorted(self._jits))


def _placed(init: Callable, mesh, specs: Callable) -> Callable:
    """``init`` jitted to lay every buffer out as ``specs`` says on
    ``mesh``: each device builds only its own rows, so a state of W
    model-sized buffers never lands whole on one device first."""
    def placed_init(params: Any, num_workers: int):
        fn = functools.partial(init, num_workers=num_workers)
        out = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           specs(jax.eval_shape(fn, params)),
                           is_leaf=lambda x: isinstance(x, P))
        return jax.jit(fn, out_shardings=out)(params)

    return placed_init


def _ef_op(ops, comp: comm_mod.CompressorSpec, lanes: int, *, grid: bool,
           block: int, interpret):
    """Bind the executor module's EF round-trip for one compressor:
    (payload_buf, ref, resid) -> (decompressed fp32, resid')."""
    name = {"int8": "fused_ef_int8", "topk": "fused_ef_topk"}[comp.name]
    if grid:
        name += "_grid"
    kwargs = dict(block=block, interpret=interpret)
    if comp.name == "topk":
        kwargs["k"] = comm_mod.topk_k(comp, lanes)
    return functools.partial(getattr(ops, name), **kwargs)


def _validate_overlap(cfg: VRLConfig, algo: AlgoSpec, comp_overlapped):
    """Reject config combinations the overlapped round cannot honor.
    ``comp_overlapped`` is the compressor of the sync the overlap defers
    (flat: ``compress``; hierarchical: the level-2 ``compress2``)."""
    if not cfg.overlap:
        if cfg.deadline:
            raise ValueError(
                "deadline is a property of the overlapped round; set "
                "overlap=True (--overlap) to use it")
        return
    if algo.sync in ("none", "elastic"):
        raise ValueError(
            f"overlap defers a mean-style round-closing sync; "
            f"{algo.name!r} (sync={algo.sync!r}) has none to defer")
    if not 0.0 <= cfg.deadline <= 1.0:
        raise ValueError(
            f"deadline is a per-round miss probability in [0, 1]; got "
            f"{cfg.deadline}")
    if (cfg.deadline and comp_overlapped is not None
            and not comp_overlapped.error_feedback):
        raise ValueError(
            "deadline misses park the skipped payload in the EF residual; "
            "the overlapped sync's compressor needs error_feedback=True")


def _validate_membership(cfg: VRLConfig, algo: AlgoSpec):
    if not getattr(cfg, "membership", False):
        return
    if algo.sync == "elastic":
        raise ValueError(
            "membership composes with mean-style syncs; easgd's center "
            f"update assumes a fixed worker count — {algo.name!r} cannot "
            "run with membership=True")


# Adam moment/bias-correction bases.  Must equal optimizers.adam's defaults
# (the reference executor) — the kernel gets these explicitly so the moment
# update and the bias correction can never use different betas.
_ADAM_B1, _ADAM_B2 = 0.9, 0.999


def _inner_kind(cfg: VRLConfig) -> Tuple[str, float]:
    """Mirror optimizers.make_inner dispatch for the fused kernels."""
    if cfg.inner_optimizer == "sgd":
        if cfg.momentum:
            return "momentum", cfg.momentum
        return "sgd", 0.0
    if cfg.inner_optimizer == "momentum":
        return "momentum", cfg.momentum or 0.9
    if cfg.inner_optimizer == "adam":
        return "adam", 0.0
    raise ValueError(cfg.inner_optimizer)


_MOMENT_DTYPES = ("float32", "bfloat16")


def _moment_opts(cfg: VRLConfig, kind: str):
    """Resolve (moment storage dtype, SM3 active) for the fused engine.

    The kernels compute fp32 in-register regardless; ``moment_dtype``
    only picks what persists between steps, so "float32" is bitwise the
    original path.  SM3 factors Adam's second moment only — sgd/momentum
    configs carry no nu, so the flag is inert there (same as the
    reference ``optimizers.adam``)."""
    name = getattr(cfg, "moment_dtype", "float32")
    if name not in _MOMENT_DTYPES:
        raise ValueError(f"unknown moment_dtype {name!r}; known: "
                         f"{_MOMENT_DTYPES}")
    sm3 = bool(getattr(cfg, "sm3", False)) and kind == "adam"
    return jnp.dtype(name), sm3


def _resolve_shard_axis(ecfg, mesh) -> Optional[str]:
    """The mesh axis the row dim splits over, or None.

    ``EngineConfig.shards > 1`` with a mesh carrying ``shard_axis`` at
    matching size activates real placement; without a mesh (or without
    the axis) the sharded row padding is layout-only — buffers stay
    device-local but hold the identical values, which is what the CPU
    parity tests exercise.  A size mismatch is a config error, loudly.
    """
    if mesh is None or ecfg.shards <= 1:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sz = sizes.get(ecfg.shard_axis, 1)
    if sz == 1:
        return None
    if sz != ecfg.shards:
        raise ValueError(
            f"mesh axis {ecfg.shard_axis!r} has size {sz} but "
            f"EngineConfig.shards={ecfg.shards}; the row dim splits into "
            f"exactly one block-aligned piece per shard device")
    return ecfg.shard_axis


def _row_axis(shard_axis, shards: int):
    """Per-leaf model-shard placement rule: the row dim (-2) splits over
    ``shard_axis`` iff its extent divides into ``shards`` whole pieces and
    is not a broadcast dim of 1.  Every flat buffer's rows are padded to a
    multiple of ``block * shards`` (``flat.make_spec``), the SM3 lane stat
    carries exactly one row per shard, and size-1 dims (pend_k, Δ2's
    intra-pod dim) fall through to replicated — so one rule covers the
    whole state."""
    def row_ax(x):
        shape = tuple(getattr(x, "shape", ()))
        if (shard_axis is not None and shards > 1 and len(shape) >= 2
                and shape[-2] > 1 and shape[-2] % shards == 0):
            return shard_axis
        return None

    return row_ax


def _state_pspecs(state, axes, shard_axis=None, shards: int = 1) -> Any:
    """shard_map PartitionSpecs: worker-stacked (ndim 3) leaves shard over
    the worker axes, (R, C) leaves (center, comm ref) and every row dim
    over the model-shard axis when one is active; scalars replicate."""
    ax = tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)
    ax = ax[0] if len(ax) == 1 else ax
    row_ax = _row_axis(shard_axis, shards)

    def one(x):
        nd = getattr(x, "ndim", 0)
        if nd == 3:
            return P(ax, row_ax(x), None)
        if nd == 2:
            return P(row_ax(x), None)
        return P(*([None] * nd))

    return jax.tree.map(one, state)


def _hier_pspecs(state: HierFlatState, pod_axis, data_axis,
                 shard_axis=None, shards: int = 1) -> HierFlatState:
    """PartitionSpecs for the pod-major state: (P, D, R, C) leaves shard
    (pod, data); the per-pod Δ2 shards only the pod axis (its intra-pod dim
    is 1); scalars replicate; row dims additionally split over the
    model-shard axis when one is active (``_row_axis``).  Compressed-sync
    buffers follow their level: per-worker residuals shard like params,
    per-pod ref1/resid2 like Δ2, the global ref2 replicates over workers
    (but shards its rows)."""
    row_ax = _row_axis(shard_axis, shards)
    wspec = lambda x: P(pod_axis, data_axis, row_ax(x), None)
    podspec = lambda x: P(pod_axis, None, row_ax(x), None)
    inner = jax.tree.map(
        lambda x: wspec(x) if getattr(x, "ndim", 0) == 4 else P(),
        state.inner)
    comm = state.comm
    cspec = ()
    if isinstance(comm, HierCommState):
        have = lambda x, f: () if isinstance(x, tuple) else f(x)
        cspec = HierCommState(resid1=have(comm.resid1, wspec),
                              ref1=have(comm.ref1, podspec),
                              resid2=have(comm.resid2, podspec),
                              ref2=have(comm.ref2,
                                        lambda x: P(row_ax(x), None)))
    ospec = ()
    if isinstance(state.overlap, OverlapState):
        # level-2 overlap buffers are per-pod (P, 1, ...): pod axis only
        ospec = OverlapState(pend=podspec(state.overlap.pend),
                             pend_k=podspec(state.overlap.pend_k))
    mspec = ()
    if isinstance(state.member, MemberState):
        mspec = MemberState(active=wspec(state.member.active),
                            n_active=P(),
                            n_pod=podspec(state.member.n_pod))
    return HierFlatState(params=wspec(state.params),
                         delta1=wspec(state.delta1),
                         delta2=podspec(state.delta2), inner=inner,
                         step=P(), last_sync1=P(), last_sync2=P(),
                         comm=cspec, overlap=ospec, member=mspec)


def state_partition_specs(state, worker_axes,
                          hier_axes: Tuple[str, str] = ("pod", "data"),
                          shard_axis=None, shards: int = 1):
    """PartitionSpec pytree for a fused-engine state (flat or hierarchical).

    The launch layer (``launch/dryrun.py``) and the HLO-collective tests use
    this to place engine states on the production mesh: flat (W, R, C)
    buffers shard their worker axis over ``worker_axes``; hierarchical
    (P, D, R, C) buffers shard pod-major over ``hier_axes``; with
    ``shard_axis``/``shards`` set, every buffer's row dim additionally
    splits over the model-shard axis (FSDP over the flat layout).
    """
    if isinstance(state, HierFlatState):
        return _hier_pspecs(state, *hier_axes, shard_axis=shard_axis,
                            shards=shards)
    return _state_pspecs(state, worker_axes, shard_axis=shard_axis,
                         shards=shards)


def make_engine(cfg: VRLConfig, template: Any, *, mesh=None,
                worker_axes: Tuple[str, ...] = ("data",)) -> Engine:
    """Build the fused engine for ``cfg.algorithm`` over ``template`` (a
    single-model pytree of arrays or ShapeDtypeStructs).

    ``mesh``: optional jax Mesh.  When given (and the worker axes span more
    than one device) the step functions run under ``shard_map`` over
    ``worker_axes`` and the sync's model average is a single ``psum`` of the
    flat buffer; otherwise the worker axis is purely local and the average
    is a ``jnp.mean`` (the single-device fallback).
    """
    algo = get_spec(cfg.algorithm)
    ecfg = cfg.engine
    fspec = flat.make_spec(template, lanes=ecfg.lanes, block=ecfg.block,
                           max_waste=ecfg.max_pad_waste, shards=ecfg.shards)
    interpret = (vu.default_interpret() if ecfg.interpret is None
                 else ecfg.interpret)
    backend = resolve_backend(cfg)
    if backend == "reference":
        raise ValueError("make_engine builds the flat-buffer executors; "
                         "the reference tree path lives in train_loop "
                         "(update_backend='reference')")
    if cfg.update_backend == "fused" and interpret:
        warnings.warn(
            f"update_backend='fused' runs interpret-mode Pallas on the "
            f"{jax.default_backend()!r} backend (orders of magnitude "
            f"slower); use update_backend='auto' to get the XLA executor "
            f"here", stacklevel=2)
    ops = vu if backend == "fused" else xu
    block = fspec.block
    kind, beta = _inner_kind(cfg)
    mdt, sm3 = _moment_opts(cfg, kind)
    lr, wd = cfg.learning_rate, cfg.weight_decay
    delta_dt = jnp.dtype(cfg.delta_dtype)
    comp, _comp2 = comm_mod.resolve_pair(cfg)
    _validate_overlap(cfg, algo, _comp2 if algo.sync == "vrl2" else comp)
    _validate_membership(cfg, algo)
    member_on = bool(getattr(cfg, "membership", False))

    if algo.sync == "vrl2":
        return _make_hier_engine(cfg, algo, fspec, mesh=mesh, ops=ops,
                                 backend=backend, kind=kind,
                                 beta=beta, lr=lr, wd=wd, delta_dt=delta_dt,
                                 block=block, interpret=interpret,
                                 mdt=mdt, sm3=sm3)

    axis_names = None
    axis_size = 1
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        axis_size = math.prod(sizes[a] for a in worker_axes)
        if axis_size > 1:
            axis_names = tuple(worker_axes)
    shard_axis = _resolve_shard_axis(ecfg, mesh)
    on_mesh = axis_names is not None or shard_axis is not None

    def _wmean(buf, member=()):
        """Global worker mean of a (W_local, R, C) buffer -> (R, C).

        On the mesh this is THE communication event: one all-reduce over
        the flat buffer.  With a ``MemberState`` the mean runs over ACTIVE
        workers only: dead rows are excluded with a ``where`` (a multiply
        would propagate a crashed worker's NaNs as ``NaN * 0``) and the
        divisor is the state-carried active count — still the same single
        all-reduce, and bitwise the unmasked mean at a full mask."""
        if isinstance(member, MemberState):
            s = jnp.sum(jnp.where(member.active > 0, buf, 0), axis=0)
            if axis_names is not None:
                s = jax.lax.psum(s, axis_names)
            # Multiply by the reciprocal rather than divide: XLA folds the
            # unmasked ``sum / W`` into ``sum * (1/W)``, and bitwise parity
            # of the full-mask program requires the same op sequence here
            # (a runtime divide rounds differently once fused downstream).
            return s * (1.0 / member.n_active)
        if axis_names is None:
            return jnp.mean(buf, axis=0)
        total = buf.shape[0] * axis_size
        s = jax.lax.psum(jnp.sum(buf, axis=0), axis_names)
        return s / total

    # ------------------------------------------------------------- init
    bias_on = use_bias(algo, cfg)
    ef_rt = (None if comp is None else
             _ef_op(ops, comp, fspec.lanes, grid=False, block=block,
                    interpret=interpret))

    def init(params: Any, num_workers: int) -> FlatWorkerState:
        flat1 = flat.flatten_tree(fspec, params)
        stacked = jnp.broadcast_to(flat1, (num_workers, *flat1.shape)).copy()
        delta = (jnp.zeros(stacked.shape, delta_dt) if algo.use_delta else ())
        bias = jnp.zeros(stacked.shape, delta_dt) if bias_on else ()
        if kind == "sgd":
            inner = ()
        elif kind == "momentum":
            inner = jnp.zeros(stacked.shape, mdt)
        elif sm3:
            # factored nu: a (W, R, 1) row stat + a (W, S, C) lane stat
            # (one lane row per model shard's row span) replace the dense
            # (W, R, C) buffer — ~R·C/(R + S·C) times smaller
            nu = SM3Pair(
                row=jnp.zeros((num_workers, fspec.rows, 1), jnp.float32),
                col=jnp.zeros((num_workers, fspec.shards, fspec.lanes),
                              jnp.float32))
            inner = AdamState(jnp.zeros(stacked.shape, mdt), nu,
                              jnp.zeros((), jnp.int32))
        else:
            inner = AdamState(jnp.zeros(stacked.shape, mdt),
                              jnp.zeros(stacked.shape, mdt),
                              jnp.zeros((), jnp.int32))
        center = flat1.astype(jnp.float32) if algo.has_center else None
        comm = ()
        if comp is not None:
            # fp32 residuals keep the EF invariant exact; ref is the shared
            # post-sync value ((R, C)) — () for S-SGD gradient compression
            resid = (jnp.zeros(stacked.shape, jnp.float32)
                     if comp.error_feedback else ())
            ref = (() if (algo.grad_all_reduce or algo.sync == "none")
                   else flat1.astype(jnp.float32))
            comm = CommState(resid=resid, ref=ref)
        overlap = ()
        if cfg.overlap:
            # pend = the initial broadcast position (everyone "transmitted"
            # x0 before step 0), so the first fold's correction is exactly
            # zero; pend_k = 1 keeps its Δ scale finite
            overlap = OverlapState(
                pend=stacked.astype(jnp.float32).copy(),
                pend_k=jnp.ones((num_workers, 1, 1), jnp.float32))
        member = ()
        if member_on:
            # everyone starts active; the count rides in state so the
            # masked means never need a second collective
            member = MemberState(
                active=jnp.ones((num_workers, 1, 1), jnp.float32),
                n_active=jnp.asarray(float(num_workers), jnp.float32))
        return FlatWorkerState(params=stacked, delta=delta, inner=inner,
                               center=center,
                               step=jnp.zeros((), jnp.int32),
                               last_sync=jnp.zeros((), jnp.int32),
                               bias=bias, comm=comm, overlap=overlap,
                               member=member)

    # ------------------------------------------------- core step functions
    # These see LOCAL shards (W_local, R, C) when shard_mapped.
    @jax.named_scope("engine.local_update")
    def _core_local(state: FlatWorkerState, g: jax.Array) -> FlatWorkerState:
        if algo.grad_all_reduce:
            if comp is not None:
                # S-SGD: the per-step gradient IS the payload (ref ≡ 0)
                e = state.comm.resid if comp.error_feedback else None
                dec, e_out = ef_rt(g, None, e)
                g = jnp.broadcast_to(_wmean(dec, state.member)[None],
                                     g.shape)
                if comp.error_feedback:
                    state = state._replace(
                        comm=state.comm._replace(resid=e_out))
            else:
                g = jnp.broadcast_to(_wmean(g, state.member)[None], g.shape)
        d = state.delta if algo.use_delta else None
        b = state.bias if bias_on else None
        if kind == "sgd":
            new_p = ops.fused_local_sgd(state.params, g, d, b=b, lr=lr,
                                        wd=wd, block=block,
                                        interpret=interpret)
            new_inner = state.inner
        elif kind == "momentum":
            new_p, new_m = ops.fused_local_momentum(
                state.params, g, d, state.inner, b=b, lr=lr, beta=beta,
                wd=wd, block=block, interpret=interpret)
            new_inner = new_m
        else:
            count = state.inner.count + 1
            t = count.astype(jnp.float32)
            scal = jnp.stack([1.0 - _ADAM_B1 ** t, 1.0 - _ADAM_B2 ** t]
                             ).reshape(1, 2).astype(jnp.float32)
            if sm3:
                new_p, new_mu, new_row, new_col = ops.fused_local_adam_sm3(
                    state.params, g, d, state.inner.mu,
                    state.inner.nu.row, state.inner.nu.col, scal, b=b,
                    lr=lr, b1=_ADAM_B1, b2=_ADAM_B2, wd=wd, block=block,
                    interpret=interpret)
                new_inner = AdamState(new_mu, SM3Pair(new_row, new_col),
                                      count)
            else:
                new_p, new_mu, new_nu = ops.fused_local_adam(
                    state.params, g, d, state.inner.mu, state.inner.nu,
                    scal, b=b, lr=lr, b1=_ADAM_B1, b2=_ADAM_B2, wd=wd,
                    block=block, interpret=interpret)
                new_inner = AdamState(new_mu, new_nu, count)
        out = state._replace(params=new_p, inner=new_inner,
                             step=state.step + 1)
        if algo.grad_all_reduce:
            out = out._replace(last_sync=state.step + 1)
        return out

    def _comp_mean(state: FlatWorkerState):
        """Compressed-drift worker mean: one fused EF round-trip pass
        (payload = p − ref + resid → decompressed + residual', residual
        donated), then the SAME single flat all-reduce — over the
        decompressed drift.  ref is shared across workers, so
        mean_i(p_i) = ref + mean_i(p_i − ref) exactly."""
        cm = state.comm
        e = cm.resid if comp.error_feedback else None
        dec, e_out = ef_rt(state.params, cm.ref, e)
        xbar = cm.ref + _wmean(dec, state.member)
        cm = CommState(resid=(e_out if comp.error_feedback else ()),
                       ref=xbar)
        return xbar, state._replace(comm=cm)

    @jax.named_scope("engine.sync")
    def _core_sync(state: FlatWorkerState) -> FlatWorkerState:
        if algo.sync == "none":
            return state._replace(last_sync=state.step)
        if algo.sync == "elastic":
            n = state.params.shape[0] * axis_size
            a = cfg.easgd_alpha / n
            if comp is not None:
                xbar, state = _comp_mean(state)
            else:
                xbar = _wmean(state.params.astype(jnp.float32))
            new_p, new_c = ops.fused_sync_easgd(
                state.params, xbar, state.center, a=a, na=n * a,
                block=block, interpret=interpret)
            return state._replace(params=new_p, center=new_c,
                                  last_sync=state.step)
        if comp is not None:
            xbar, state = _comp_mean(state)
        else:
            xbar = _wmean(state.params, state.member)
        if algo.sync == "average":
            new_p = jnp.broadcast_to(xbar[None], state.params.shape
                                     ).astype(state.params.dtype)
            return state._replace(params=new_p, last_sync=state.step)
        # "vrl"/"bvr": fused Δ (+ B) update + parameter broadcast, one pass
        k_eff = jnp.maximum(state.step - state.last_sync, 1
                            ).astype(jnp.float32)
        scal = (k_eff * lr).reshape(1, 1).astype(jnp.float32)
        if algo.sync == "bvr" and bias_on:
            new_p, new_d, new_b = ops.fused_sync_bvr(
                state.params, xbar.astype(state.params.dtype), state.delta,
                state.bias, scal, beta=cfg.bvr_beta, block=block,
                interpret=interpret)
            return state._replace(params=new_p, delta=new_d, bias=new_b,
                                  last_sync=state.step)
        new_p, new_d = ops.fused_sync_vrl(
            state.params, xbar.astype(state.params.dtype), state.delta,
            scal, block=block, interpret=interpret)
        return state._replace(params=new_p, delta=new_d,
                              last_sync=state.step)

    def _core_train(state: FlatWorkerState, g: jax.Array) -> FlatWorkerState:
        state = _core_local(state, g)
        if algo.sync == "none":
            return state
        return jax.lax.cond(
            should_sync(algo, cfg, state.step, state.last_sync),
            _core_sync, lambda s: s, state)

    def _core_round(state: FlatWorkerState, gk: jax.Array) -> FlatWorkerState:
        """k local steps under one scan over (k, W, R, C) grads, then the
        round-closing sync.  The round IS the communication period — the
        caller sizes gk (warmup's first k=1 period is a 1-step round)."""
        state, _ = jax.lax.scan(lambda s, g: (_core_local(s, g), None),
                                state, gk)
        return _core_sync(state)

    # ------------------------------------------------- overlapped round
    def _miss_mask(step: jax.Array, n: int) -> jax.Array:
        """Per-participant (n, 1) deadline-miss mask for the round ending
        at ``step``: 1 ⇒ the participant missed its capture deadline
        (simulated per participant per round — a single-host SPMD run has
        no real per-worker clock).  deadline=0 short-circuits to a
        constant at trace time, so the no-deadline program is bitwise
        identical."""
        if not cfg.deadline:
            return jnp.zeros((n, 1), jnp.float32)
        key = jax.random.fold_in(jax.random.PRNGKey(0), step)
        if axis_names is not None:
            for a in axis_names:
                key = jax.random.fold_in(key, jax.lax.axis_index(a))
        u = jax.random.uniform(key, (n, 1))
        return (u < cfg.deadline).astype(jnp.float32)

    def _fold_overlap(state: FlatWorkerState, xbar: jax.Array
                      ) -> FlatWorkerState:
        """Apply the round-START collective's (one-round-stale) mean at
        round end: fold c = x̂_stale − pend into params/Δ (+B), then
        capture the new positions for the NEXT round's collective."""
        ov = state.overlap
        k_eff = jnp.maximum(state.step - state.last_sync, 1
                            ).astype(jnp.float32)
        km = _miss_mask(state.step, ov.pend.shape[0])          # (W_l, 1)
        inv = 1.0 / (ov.pend_k[:, :, 0] * lr)                  # (W_l, 1)
        wscal = jnp.concatenate([inv, km], axis=1).astype(jnp.float32)
        km3 = km[:, :, None]
        # a missed capture keeps pend and stretches the period it covers
        new_pend_k = km3 * (ov.pend_k + k_eff) + (1.0 - km3) * k_eff
        capture = comp is None
        xb = xbar.astype(state.params.dtype)
        if algo.sync == "average":
            out = ops.fused_fold_overlap_avg(
                state.params, xb, ov.pend, wscal, capture=capture,
                block=block, interpret=interpret)
            state = state._replace(params=out[0])
            new_pend = out[1] if capture else None
        elif algo.sync == "bvr" and bias_on:
            out = ops.fused_fold_overlap_bvr(
                state.params, xb, ov.pend, state.delta, state.bias,
                wscal, beta=cfg.bvr_beta, capture=capture, block=block,
                interpret=interpret)
            state = state._replace(params=out[0], delta=out[1],
                                   bias=out[2])
            new_pend = out[3] if capture else None
        else:
            out = ops.fused_fold_overlap(
                state.params, xb, ov.pend, state.delta, wscal,
                capture=capture, block=block, interpret=interpret)
            state = state._replace(params=out[0], delta=out[1])
            new_pend = out[2] if capture else None
        if comp is not None:
            # compressed capture: transmit the folded position's drift
            # against the stale mean through the EF round-trip; a missed
            # deadline returns the whole decompressed payload to the
            # residual (the worker never actually transmitted it)
            cm = state.comm
            e = cm.resid if comp.error_feedback else None
            dec, e_out = ef_rt(state.params, xbar, e)
            sent = xbar[None] + dec            # (W_l, R, C) absolute pos
            new_pend = km3 * ov.pend + (1.0 - km3) * sent
            resid = (e_out + km3 * dec if comp.error_feedback else ())
            state = state._replace(comm=CommState(resid=resid, ref=xbar))
        return state._replace(overlap=OverlapState(new_pend, new_pend_k),
                              last_sync=state.step)

    def _core_round_begin(state: FlatWorkerState) -> jax.Array:
        # masked: a dead worker's pend is retired from the collective
        # (not retransmitted forever) until it rejoins with a fresh one
        return _wmean(state.overlap.pend, state.member)

    def _core_round_overlap(state: FlatWorkerState, gk: jax.Array
                            ) -> FlatWorkerState:
        """Overlapped round: THE sync all-reduce is issued FIRST, over the
        previous boundary's transmitted positions — its operands are ready
        before the scan starts, so the scheduler can run the collective
        concurrently with the k local steps — and its stale result is
        folded in at the end.  Still one sync all-reduce per k steps."""
        xbar = _core_round_begin(state)
        state, _ = jax.lax.scan(lambda s, g: (_core_local(s, g), None),
                                state, gk)
        return _fold_overlap(state, xbar)

    # --------------------------------------------- membership repair
    def _core_set_membership(state: FlatWorkerState, new_active: jax.Array
                             ) -> FlatWorkerState:
        """Repair the state invariants for a changed active set.

        Mask-value-driven (the mask is an operand, not a trace constant),
        so one jit covers every drop/rejoin pattern.  Continuing workers:
        Δ (and B) recentred to mean zero over the continuing set —
        algebraically identical to redistributing each dropped worker's Δ
        across the survivors (Σ_cont Δ = −Σ_dropped Δ before the repair),
        but computed without ever reading a dropped row, so a crashed
        worker's NaNs cannot leak.  Dropped + rejoining workers: params
        (and overlap pend) re-seeded from the continuing consensus x̂;
        Δ/B/moments/EF residuals zeroed."""
        def _gsum(x):
            s = jnp.sum(x, axis=0)
            if axis_names is not None:
                s = jax.lax.psum(s, axis_names)
            return s

        old = state.member.active                          # (W_l, 1, 1)
        cont = old * new_active
        keep = cont > 0
        n_cont = jnp.maximum(jnp.sum(_gsum(cont)), 1.0)
        n_new = jnp.sum(_gsum(new_active))
        xhat = _gsum(jnp.where(keep, state.params.astype(jnp.float32), 0.0)
                     ) / n_cont                            # (R, C)
        params = jnp.where(keep, state.params,
                           xhat.astype(state.params.dtype)[None])

        def recenter(buf):
            shift = _gsum(jnp.where(keep, buf, 0)) / n_cont
            return jnp.where(keep, buf - shift.astype(buf.dtype)[None],
                             jnp.zeros((), buf.dtype))

        delta = recenter(state.delta) if algo.use_delta else state.delta
        bias = recenter(state.bias) if bias_on else state.bias
        inner = jax.tree.map(
            lambda x: (jnp.where(keep, x, jnp.zeros((), x.dtype))
                       if getattr(x, "ndim", 0) == 3 else x), state.inner)
        comm = state.comm
        if isinstance(comm, CommState) and not isinstance(comm.resid,
                                                          tuple):
            comm = comm._replace(resid=jnp.where(keep, comm.resid, 0.0))
        ov = state.overlap
        if isinstance(ov, OverlapState):
            ov = OverlapState(pend=jnp.where(keep, ov.pend, xhat[None]),
                              pend_k=jnp.where(keep, ov.pend_k, 1.0))
        member = MemberState(active=new_active, n_active=n_new)
        return state._replace(params=params, delta=delta, bias=bias,
                              inner=inner, comm=comm, overlap=ov,
                              member=member)

    # --------------------------------------------- cohort drift recentre
    def _core_recenter_drift(state: FlatWorkerState) -> FlatWorkerState:
        """Re-zero Σ Δ (and Σ B) over the rows currently in the buffers.

        Client sampling gathers a cohort of W rows out of M client rows;
        each client's Δ was recentred against ALL clients, so the cohort's
        corrections sum to the cohort mean rather than zero — the sync
        math would then drag x̂ by that mean every round.  Subtracting the
        cohort mean restores Σ Δ = 0 (the ``set_membership`` repair's
        recentre, minus the churn handling), masked over active rows when
        a ``MemberState`` rides along so a crashed slot's NaNs can't leak.
        """
        member = state.member
        keep = (member.active > 0 if isinstance(member, MemberState)
                else None)

        def recenter(buf):
            shift = _wmean(buf, member)
            if keep is None:
                return buf - shift.astype(buf.dtype)[None]
            return jnp.where(keep, buf - shift.astype(buf.dtype)[None],
                             buf)

        delta = recenter(state.delta) if algo.use_delta else state.delta
        bias = recenter(state.bias) if bias_on else state.bias
        return state._replace(delta=delta, bias=bias)

    # --------------------------------------------------------- diagnostics
    # The record layout is decided at TRACE time from the config — the
    # shard_map out_specs must be a statically-known pytree, so which keys
    # exist can never depend on runtime values.
    ef_on = comp is not None and bool(getattr(comp, "error_feedback",
                                              False))
    diag_keys = ["params_rms", "drift_sq_mean", "drift_max",
                 "drift_per_worker", "nonfinite_workers"]
    if algo.use_delta:
        diag_keys += ["delta_residual", "zeta_sq_proxy"]
    if bias_on:
        diag_keys += ["bias_residual"]
    if ef_on:
        diag_keys += ["ef_resid_rms"]
    if kind != "sgd":
        diag_keys += ["mu_rms"]
    if kind == "adam":
        diag_keys += ["nu_rms"]
    red_axes = tuple(axis_names or ()) + ((shard_axis,)
                                          if shard_axis is not None else ())

    def _core_diagnostics(state: FlatWorkerState) -> dict:
        """Algorithm-health figures in ONE read-only pass.

        Runs OUTSIDE the compiled round (its own jit, --log-every
        cadence), so its handful of collectives — worker-axis psums plus
        the shard-axis row reductions — never touch the round's
        one-all-reduce HLO contract.

        Paper grounding: ``zeta_sq_proxy`` is the across-worker
        dispersion of the control variates, (1/n) Σᵢ ‖Δᵢ − Δ̄‖² — the
        analysis has Δᵢ tracking ∇Fᵢ − ∇F, so this is the runtime proxy
        for ζ², the inter-worker gradient variance whose dependency
        VRL-SGD eliminates.  (Post-sync params COINCIDE under broadcast
        syncs, so a between-round drift dispersion would measure ~0 and
        proxy nothing; drift is still reported because it is the
        meaningful dispersion under overlap / membership / EASGD, where
        params do not re-coincide.)  ``delta_residual`` is
        ‖(1/n) Σᵢ Δᵢ‖∞ — the Σ Δ = 0 invariant's residual
        (``bias_residual`` the BVR Σ B = 0 twin); both sit at
        float-noise level on a healthy run.

        Dead rows are excluded with ``where`` (never multiply — a
        crashed worker's NaNs would survive ``NaN * 0``), so a masked-
        out slot neither counts as non-finite nor drags any mean.
        """
        member = state.member
        masked = isinstance(member, MemberState)
        n = (member.n_active if masked
             else jnp.asarray(float(state.params.shape[0] * axis_size),
                              jnp.float32))

        def keep(buf):
            if not masked:
                return buf.astype(jnp.float32)
            return jnp.where(member.active > 0, buf.astype(jnp.float32),
                             0.0)

        def _gsum(x):                       # scalar sum over EVERY axis
            s = jnp.sum(x)
            return jax.lax.psum(s, red_axes) if red_axes else s

        def _gmax(x):                       # scalar max over EVERY axis
            m = jnp.max(x)
            return jax.lax.pmax(m, red_axes) if red_axes else m

        def _per_worker(x):                 # (W_l, R_l, C) -> (W_l,)
            s = jnp.sum(x, axis=(1, 2))
            if shard_axis is not None:
                s = jax.lax.psum(s, shard_axis)
            return s

        def _wsum(x):                       # worker-axis sum -> (R_l, C)
            s = jnp.sum(x, axis=0)
            if axis_names is not None:
                s = jax.lax.psum(s, axis_names)
            return s

        def _wscalar(s):                    # scalar sum over worker axes
            return (jax.lax.psum(s, axis_names) if axis_names is not None
                    else s)

        elems = float(fspec.rows * fspec.lanes)  # padded per-worker count

        out = {}
        p32 = keep(state.params)
        bad = _per_worker((~jnp.isfinite(p32)).astype(jnp.float32))
        out["nonfinite_workers"] = _wscalar(
            jnp.sum((bad > 0).astype(jnp.float32)))
        out["params_rms"] = jnp.sqrt(_gsum(p32 * p32) / (n * elems))
        xhat = _wsum(p32) * (1.0 / n)
        dev = keep(p32 - xhat[None])
        drift_w = _per_worker(dev * dev)    # ‖xᵢ − x̂‖² per worker
        out["drift_sq_mean"] = _wscalar(jnp.sum(drift_w)) * (1.0 / n)
        out["drift_max"] = jnp.sqrt(_gmax(drift_w))
        out["drift_per_worker"] = jnp.sqrt(drift_w)

        def invariant(buf, res_key, disp_key=None):
            b32 = keep(buf)
            s = _wsum(b32)                  # Σᵢ over active workers
            out[res_key] = _gmax(jnp.abs(s)) * (1.0 / n)
            if disp_key is not None:
                d = keep(b32 - (s * (1.0 / n))[None])
                out[disp_key] = _gsum(d * d) * (1.0 / n)

        if algo.use_delta:
            invariant(state.delta, "delta_residual", "zeta_sq_proxy")
        if bias_on:
            invariant(state.bias, "bias_residual")
        if ef_on:
            r32 = keep(state.comm.resid)
            out["ef_resid_rms"] = jnp.sqrt(_gsum(r32 * r32) / (n * elems))
        if kind != "sgd":
            m32 = keep(state.inner if kind == "momentum"
                       else state.inner.mu)
            out["mu_rms"] = jnp.sqrt(_gsum(m32 * m32) / (n * elems))
        if kind == "adam":
            if sm3:
                row32 = keep(state.inner.nu.row)
                col32 = keep(state.inner.nu.col)
                cnt = n * float(fspec.rows + fspec.shards * fspec.lanes)
                out["nu_rms"] = jnp.sqrt((_gsum(row32 * row32)
                                          + _gsum(col32 * col32)) / cnt)
            else:
                n32 = keep(state.inner.nu)
                out["nu_rms"] = jnp.sqrt(_gsum(n32 * n32) / (n * elems))
        return {k: out[k] for k in diag_keys}

    # ----------------------------------------------------- shard_map wrap
    ax = None
    if axis_names is not None:
        ax = axis_names[0] if len(axis_names) == 1 else axis_names

    def _specs(state):
        return _state_pspecs(state, axis_names, shard_axis=shard_axis,
                             shards=ecfg.shards)

    def _sharded(fn, gspec: Optional[P] = None):
        if not on_mesh:
            return fn

        def wrapped(state, *rest):
            sspec = _specs(state)
            in_specs = (sspec,) if gspec is None else (sspec, gspec)
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=sspec,
                                 check_vma=False)(state, *rest)

        return wrapped

    local_core = _sharded(_core_local, gspec=P(ax, shard_axis, None))
    sync_core = _sharded(_core_sync)
    recenter_core = _sharded(_core_recenter_drift)

    def diagnostics(state: FlatWorkerState) -> dict:
        """One read-only jitted pass of algorithm-health scalars plus a
        (W,) per-worker drift vector (see ``_core_diagnostics``).  Jit
        WITHOUT donation — it must not consume the state."""
        if not on_mesh:
            return _core_diagnostics(state)
        out_specs = {k: (P(ax) if k == "drift_per_worker" else P())
                     for k in diag_keys}
        return jax.shard_map(_core_diagnostics, mesh=mesh,
                             in_specs=(_specs(state),),
                             out_specs=out_specs,
                             check_vma=False)(state)
    train_core = _sharded(_core_train, gspec=P(ax, shard_axis, None))
    round_core = _sharded(_core_round_overlap if cfg.overlap
                          else _core_round,
                          gspec=P(None, ax, shard_axis, None))

    round_begin = round_fold = None
    if cfg.overlap:
        def round_begin(state, k: int = 0):
            """The round-START collective: the stale mean the round will
            fold (k is unused by the flat engine; the hierarchical twin
            needs it for the k2 cadence)."""
            del k
            if not on_mesh:
                return _core_round_begin(state)
            sspec = _specs(state)
            return jax.shard_map(
                _core_round_begin, mesh=mesh, in_specs=(sspec,),
                out_specs=P(shard_axis, None), check_vma=False)(state)

        def round_fold(state, xbar):
            """Fold ``round_begin``'s result at round end (one round
            stale by the local steps run in between)."""
            if not on_mesh:
                return _fold_overlap(state, xbar)
            sspec = _specs(state)
            return jax.shard_map(
                _fold_overlap, mesh=mesh,
                in_specs=(sspec, P(shard_axis, None)), out_specs=sspec,
                check_vma=False)(state, xbar)

    set_membership = None
    if member_on:
        member_core = _sharded(_core_set_membership,
                               gspec=P(ax, None, None))

        def set_membership(state: FlatWorkerState, active
                           ) -> FlatWorkerState:
            """Change the active set to ``active`` ((W,) bools/floats),
            repairing the invariants: Σ Δ (and Σ B) over the new active
            set is exactly zero, rejoiners restart from the continuing
            consensus.  Call between rounds (jit with
            donate_argnums=(0,)); one jit covers every mask value."""
            m = jnp.asarray(active, jnp.float32).reshape(-1)[:, None, None]
            return member_core(state, m)

    # --------------------------------------------------------- public API
    def _gbuf(grads: Any) -> jax.Array:
        return flat.flatten_stacked(fspec, grads, dtype=fspec.dtype)

    def local_step(state: FlatWorkerState, grads: Any) -> FlatWorkerState:
        return local_core(state, _gbuf(grads))

    def train_step(state: FlatWorkerState, grads: Any) -> FlatWorkerState:
        return train_core(state, _gbuf(grads))

    def sync(state: FlatWorkerState) -> FlatWorkerState:
        return sync_core(state)

    def round_step(state: FlatWorkerState, grads_k: Any) -> FlatWorkerState:
        """One communication round: scan k local steps + sync, one jit unit.

        ``grads_k``: worker-stacked grads pytree with an extra leading step
        axis ((k, W, ...) leaves).  Jit with ``donate_argnums=(0,)`` so the
        flat state buffers update in place across rounds.
        """
        gk = jax.vmap(
            lambda t: flat.flatten_stacked(fspec, t, dtype=fspec.dtype)
        )(grads_k)
        return round_core(state, gk)

    def round_step_flat(state: FlatWorkerState, gk: jax.Array
                        ) -> FlatWorkerState:
        """``round_step`` over an already-flattened (k, W, R, C) grads
        buffer — no pytree-flatten pass (the layout-native hot path)."""
        return round_core(state, gk)

    def params_tree(state: FlatWorkerState) -> Any:
        """Worker-stacked parameter pytree view (for the model forward)."""
        return flat.unflatten_stacked(fspec, state.params)

    def avg_model(state: FlatWorkerState) -> Any:
        if isinstance(state.member, MemberState):
            s = jnp.sum(jnp.where(state.member.active > 0, state.params,
                                  0), axis=0)
            return flat.unflatten_tree(
                fspec, s * (1.0 / state.member.n_active))
        return flat.unflatten_tree(fspec, jnp.mean(state.params, axis=0))

    return Engine(algorithm=cfg.algorithm, spec=fspec, algo=algo,
                  init=_placed(init, mesh, _specs) if on_mesh else init,
                  train_step=train_step, local_step=local_step,
                  sync=sync, average_model=avg_model,
                  params_tree=params_tree,
                  round_step=round_step, round_end=sync,
                  round_step_flat=round_step_flat,
                  round_begin=round_begin, round_fold=round_fold,
                  backend=backend, interpret=interpret,
                  # store the resolve_pair form verbatim (level 2 is
                  # meaningless for flat algorithms but keeping the pair
                  # canonical means pair_meta(cfg) == pair_meta(engine
                  # .compressors) — checkpoint metadata agrees whichever
                  # form a caller derives it from)
                  compressors=(comp, _comp2),
                  set_membership=set_membership,
                  recenter_drift=recenter_core,
                  diagnostics=diagnostics)


# ================================================ fused executor ("vrl2")
def _make_hier_engine(cfg: VRLConfig, algo: AlgoSpec, fspec: flat.FlatSpec,
                      *, mesh, ops, backend: str, kind: str, beta: float,
                      lr: float, wd: float, delta_dt, block: int,
                      interpret: bool, mdt=jnp.float32,
                      sm3: bool = False) -> Engine:
    """The two-level engine over pod-major (P, D, R, C) flat buffers.

    Level-1 sync averages within each pod (one psum over the intra-pod mesh
    axis) and folds the Δ1 update into the same fused pass; level-2
    averages across pods (one psum over the cross-pod axis) and folds the
    Δ2 update in.  Local steps touch no cross-worker axis at all.
    """
    hcfg = hier_config(cfg)
    p_total, d_total = hcfg.grid
    k1, k2 = hcfg.k1, hcfg.k2
    comp1, comp2 = comm_mod.resolve_pair(cfg)
    ef1_rt = (None if comp1 is None else
              _ef_op(ops, comp1, fspec.lanes, grid=True, block=block,
                     interpret=interpret))
    ef2_rt = (None if comp2 is None else
              _ef_op(ops, comp2, fspec.lanes, grid=False, block=block,
                     interpret=interpret))
    pod_axis = data_axis = None
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if sizes.get(hcfg.axes[0], 1) > 1:
            pod_axis = hcfg.axes[0]
        if sizes.get(hcfg.axes[1], 1) > 1:
            data_axis = hcfg.axes[1]
    shard_axis = _resolve_shard_axis(cfg.engine, mesh)

    member_on = bool(getattr(cfg, "membership", False))

    def _pod_mean(buf, member=()):
        """(P_l, D_l, R, C) -> (P_l, 1, R, C).  THE intra-pod all-reduce.

        Masked form: mean over each pod's ACTIVE members (state-carried
        per-pod counts); an all-dead pod divides by 1 and is excluded
        from the cross-pod mean by its zero count."""
        if isinstance(member, MemberState):
            s = jnp.sum(jnp.where(member.active > 0, buf, 0), axis=1,
                        keepdims=True)
            if data_axis is not None:
                s = jax.lax.psum(s, data_axis)
            # reciprocal-multiply, matching XLA's fold of the unmasked
            # constant divide (bitwise parity at full mask)
            return s * (1.0 / jnp.maximum(member.n_pod, 1.0))
        s = jnp.sum(buf, axis=1, keepdims=True)
        if data_axis is not None:
            s = jax.lax.psum(s, data_axis)
        return s / d_total

    def _cross_mean(pod_avg, member=()):
        """(P_l, 1, R, C) pod averages -> (R, C).  THE cross-pod
        all-reduce.

        Masked form: uniform mean over ALIVE pods — the weighting that
        keeps Σ_p Δ2 = 0 exact through pod-level churn (``n_active`` is
        the alive-pod count on the hierarchical engine)."""
        if isinstance(member, MemberState):
            alive = member.n_pod > 0
            s = jnp.sum(jnp.where(alive, pod_avg, 0), axis=(0, 1))
            if pod_axis is not None:
                s = jax.lax.psum(s, pod_axis)
            # reciprocal-multiply (see _pod_mean): full-mask bitwise parity
            return s * (1.0 / member.n_active)
        s = jnp.sum(pod_avg, axis=(0, 1))
        if pod_axis is not None:
            s = jax.lax.psum(s, pod_axis)
        return s / p_total

    # ------------------------------------------------------------- init
    def init(params: Any, num_workers: int) -> HierFlatState:
        if num_workers != p_total * d_total:
            raise ValueError(
                f"hier grid {hcfg.grid} holds {p_total * d_total} workers, "
                f"init asked for {num_workers}")
        flat1 = flat.flatten_tree(fspec, params)
        stacked = jnp.broadcast_to(
            flat1, (p_total, d_total, *flat1.shape)).copy()
        delta1 = jnp.zeros(stacked.shape, delta_dt)
        delta2 = jnp.zeros((p_total, 1, *flat1.shape), delta_dt)
        if kind == "sgd":
            inner = ()
        elif kind == "momentum":
            inner = jnp.zeros(stacked.shape, mdt)
        elif sm3:
            nu = SM3Pair(
                row=jnp.zeros((p_total, d_total, fspec.rows, 1),
                              jnp.float32),
                col=jnp.zeros((p_total, d_total, fspec.shards, fspec.lanes),
                              jnp.float32))
            inner = AdamState(jnp.zeros(stacked.shape, mdt), nu,
                              jnp.zeros((), jnp.int32))
        else:
            inner = AdamState(jnp.zeros(stacked.shape, mdt),
                              jnp.zeros(stacked.shape, mdt),
                              jnp.zeros((), jnp.int32))
        comm = ()
        if comp1 is not None or comp2 is not None:
            comm = HierCommState(
                resid1=(jnp.zeros(stacked.shape, jnp.float32)
                        if comp1 and comp1.error_feedback else ()),
                ref1=(jnp.broadcast_to(flat1.astype(jnp.float32),
                                       (p_total, 1, *flat1.shape)).copy()
                      if comp1 else ()),
                resid2=(jnp.zeros((p_total, 1, *flat1.shape), jnp.float32)
                        if comp2 and comp2.error_feedback else ()),
                ref2=(flat1.astype(jnp.float32) if comp2 else ()))
        overlap = ()
        if cfg.overlap:
            # per-pod transmitted positions; pend = x0 so the first
            # level-2 fold's correction is exactly zero
            overlap = OverlapState(
                pend=jnp.broadcast_to(flat1.astype(jnp.float32),
                                      (p_total, 1, *flat1.shape)).copy(),
                pend_k=jnp.ones((p_total, 1, 1, 1), jnp.float32))
        member = ()
        if member_on:
            member = MemberState(
                active=jnp.ones((p_total, d_total, 1, 1), jnp.float32),
                n_active=jnp.asarray(float(p_total), jnp.float32),
                n_pod=jnp.full((p_total, 1, 1, 1), float(d_total),
                               jnp.float32))
        return HierFlatState(params=stacked, delta1=delta1, delta2=delta2,
                             inner=inner, step=jnp.zeros((), jnp.int32),
                             last_sync1=jnp.zeros((), jnp.int32),
                             last_sync2=jnp.zeros((), jnp.int32),
                             comm=comm, overlap=overlap, member=member)

    # ------------------------------------------------- core step functions
    @jax.named_scope("engine.local_update")
    def _core_local(state: HierFlatState, g: jax.Array) -> HierFlatState:
        if kind == "sgd":
            new_p = ops.fused_hier_local_sgd(
                state.params, g, state.delta1, state.delta2, lr=lr, wd=wd,
                block=block, interpret=interpret)
            new_inner = state.inner
        elif kind == "momentum":
            new_p, new_inner = ops.fused_hier_local_momentum(
                state.params, g, state.delta1, state.delta2, state.inner,
                lr=lr, beta=beta, wd=wd, block=block, interpret=interpret)
        else:
            count = state.inner.count + 1
            t = count.astype(jnp.float32)
            scal = jnp.stack([1.0 - _ADAM_B1 ** t, 1.0 - _ADAM_B2 ** t]
                             ).reshape(1, 2).astype(jnp.float32)
            if sm3:
                new_p, new_mu, new_row, new_col = \
                    ops.fused_hier_local_adam_sm3(
                        state.params, g, state.delta1, state.delta2,
                        state.inner.mu, state.inner.nu.row,
                        state.inner.nu.col, scal, lr=lr, b1=_ADAM_B1,
                        b2=_ADAM_B2, wd=wd, block=block,
                        interpret=interpret)
                new_inner = AdamState(new_mu, SM3Pair(new_row, new_col),
                                      count)
            else:
                new_p, new_mu, new_nu = ops.fused_hier_local_adam(
                    state.params, g, state.delta1, state.delta2,
                    state.inner.mu, state.inner.nu, scal, lr=lr,
                    b1=_ADAM_B1, b2=_ADAM_B2, wd=wd, block=block,
                    interpret=interpret)
                new_inner = AdamState(new_mu, new_nu, count)
        return state._replace(params=new_p, inner=new_inner,
                              step=state.step + 1)

    @jax.named_scope("engine.sync")
    def _core_sync1(state: HierFlatState) -> HierFlatState:
        k_eff = jnp.maximum(state.step - state.last_sync1, 1
                            ).astype(jnp.float32)
        if comp1 is not None:
            # compressed intra-pod drift: per-pod ref1 is shared within
            # each averaging group, so the pod mean reconstructs exactly
            cm = state.comm
            e = cm.resid1 if comp1.error_feedback else None
            dec, e_out = ef1_rt(state.params, cm.ref1, e)
            xbar = cm.ref1 + _pod_mean(dec, state.member)
            state = state._replace(comm=cm._replace(
                ref1=xbar,
                resid1=(e_out if comp1.error_feedback else ())))
        else:
            xbar = _pod_mean(state.params, state.member)
        scal = (k_eff * lr).reshape(1, 1).astype(jnp.float32)
        new_p, new_d1 = ops.fused_sync_hier1(
            state.params, xbar.astype(state.params.dtype), state.delta1,
            scal, block=block, interpret=interpret)
        return state._replace(params=new_p, delta1=new_d1,
                              last_sync1=state.step)

    @jax.named_scope("engine.sync")
    def _core_sync2(state: HierFlatState) -> HierFlatState:
        # Assumes a level-1 sync at this step: params ARE the pod averages,
        # so the global mean needs only the cross-pod axis.
        k_eff = jnp.maximum(state.step - state.last_sync2, 1
                            ).astype(jnp.float32)
        if comp2 is not None:
            # compressed cross-pod drift against the global ref2 — the
            # slow-DCI-tier payload, typically compressed the hardest
            cm = state.comm
            pod = state.params[:, 0]                    # (P_l, R, C)
            e = (cm.resid2[:, 0] if comp2.error_feedback else None)
            dec, e_out = ef2_rt(pod, cm.ref2, e)
            glob = cm.ref2 + _cross_mean(dec[:, None], state.member)
            state = state._replace(comm=cm._replace(
                ref2=glob,
                resid2=(e_out[:, None] if comp2.error_feedback else ())))
        else:
            glob = _cross_mean(state.params[:, :1], state.member)
        if comp1 is not None:
            # level-2 moves every worker to x̂: re-anchor ref1 so the next
            # intra-pod payload is small again
            cm = state.comm
            state = state._replace(comm=cm._replace(ref1=jnp.broadcast_to(
                glob.astype(jnp.float32), cm.ref1.shape)))
        scal = (k_eff * lr).reshape(1, 1).astype(jnp.float32)
        new_p, new_d2 = ops.fused_sync_hier2(
            state.params, glob.astype(state.params.dtype), state.delta2,
            scal, block=block, interpret=interpret)
        return state._replace(params=new_p, delta2=new_d2,
                              last_sync2=state.step)

    def _core_sync(state: HierFlatState) -> HierFlatState:
        return _core_sync2(_core_sync1(state))

    def _core_train(state: HierFlatState, g: jax.Array) -> HierFlatState:
        state = _core_local(state, g)
        do1 = (state.step - state.last_sync1) >= k1
        do2 = (state.step - state.last_sync2) >= k2
        state = jax.lax.cond(do1 | do2, _core_sync1, lambda s: s, state)
        return jax.lax.cond(do2, _core_sync2, lambda s: s, state)

    def _core_round_end(state: HierFlatState) -> HierFlatState:
        """Round-closing sync: a round is one k1 period, so level-1 always
        fires; level-2 fires whenever the k2 cadence is due (k2 % k1 == 0,
        checked at the public boundary — the per-step oracle is
        ``_core_train``)."""
        state = _core_sync1(state)
        do2 = (state.step - state.last_sync2) >= k2
        return jax.lax.cond(do2, _core_sync2, lambda s: s, state)

    def _core_round(state: HierFlatState, gk: jax.Array) -> HierFlatState:
        state, _ = jax.lax.scan(lambda s, g: (_core_local(s, g), None),
                                state, gk)
        return _core_round_end(state)

    # ------------------------------------------------- overlapped round
    # Only the cross-pod sync2 — the slow DCI tier the roofline prices —
    # is overlapped; the intra-pod sync1 stays blocking (ICI is cheap and
    # the level-2 fold needs post-sync1 pod-uniform params).
    def _miss_mask2(step: jax.Array, n: int) -> jax.Array:
        """Per-pod (n, 1) deadline-miss mask (level 2's participants are
        pods).  Same contract as the flat ``_miss_mask``."""
        if not cfg.deadline:
            return jnp.zeros((n, 1), jnp.float32)
        key = jax.random.fold_in(jax.random.PRNGKey(0), step)
        if pod_axis is not None:
            key = jax.random.fold_in(key, jax.lax.axis_index(pod_axis))
        u = jax.random.uniform(key, (n, 1))
        return (u < cfg.deadline).astype(jnp.float32)

    def _core_round_begin(state: HierFlatState, k: int) -> jax.Array:
        """The level-2 collective issued at round START — only when this
        round's closing step will land on the k2 cadence (the fold's
        matching cond recomputes the same predicate after the scan
        advanced ``step`` by k); otherwise zeros, which the fold never
        reads."""
        do2 = (state.step + k - state.last_sync2) >= k2
        zeros = jnp.zeros(state.overlap.pend.shape[2:], jnp.float32)
        return jax.lax.cond(
            do2, lambda s: _cross_mean(s.overlap.pend, s.member),
            lambda s: zeros, state)

    def _fold2(state: HierFlatState, glob: jax.Array) -> HierFlatState:
        """Apply the stale cross-pod mean: c_p = x̂_stale − pend2_p folds
        into every worker of pod p (post-sync1, so the whole pod moves
        together), Δ2 updates over the period pend covered, and the new
        per-pod positions are captured for the next level-2 collective."""
        ov = state.overlap
        k_eff = jnp.maximum(state.step - state.last_sync2, 1
                            ).astype(jnp.float32)
        km = _miss_mask2(state.step, ov.pend.shape[0])         # (P_l, 1)
        inv = 1.0 / (ov.pend_k[:, 0, :, 0] * lr)               # (P_l, 1)
        wscal = jnp.concatenate([inv, km], axis=1).astype(jnp.float32)
        km4 = km[:, :, None, None]
        new_pend_k = km4 * (ov.pend_k + k_eff) + (1.0 - km4) * k_eff
        capture = comp2 is None
        if comp1 is not None:
            # the fold shifts every worker of pod p by c_p: shift the
            # shared intra-pod reference the same way so the next
            # level-1 payload stays small
            c_p = glob[None, None] - ov.pend
            state = state._replace(
                comm=state.comm._replace(ref1=state.comm.ref1 + c_p))
        out = ops.fused_fold_overlap_hier2(
            state.params, glob.astype(state.params.dtype), ov.pend,
            state.delta2, wscal, capture=capture, block=block,
            interpret=interpret)
        state = state._replace(params=out[0], delta2=out[1])
        if capture:
            new_pend = out[2]
        else:
            # compressed level-2 capture: EF round-trip of the folded
            # pod position's drift against the stale global mean
            cm = state.comm
            pod = state.params[:, 0]                         # (P_l, R, C)
            e = cm.resid2[:, 0] if comp2.error_feedback else None
            dec, e_out = ef2_rt(pod, glob, e)
            sent = glob[None] + dec
            new_pend = km4 * ov.pend + (1.0 - km4) * sent[:, None]
            resid2 = ((e_out + km[:, :, None] * dec)[:, None]
                      if comp2.error_feedback else ())
            state = state._replace(comm=cm._replace(ref2=glob,
                                                    resid2=resid2))
        return state._replace(overlap=OverlapState(new_pend, new_pend_k),
                              last_sync2=state.step)

    def _core_round_end_overlap(state: HierFlatState, glob: jax.Array
                                ) -> HierFlatState:
        """Round-closing sync under overlap: the blocking level-1 sync,
        then — iff this step lands on the k2 cadence — the stale level-2
        fold of the round-START collective's result."""
        state = _core_sync1(state)
        do2 = (state.step - state.last_sync2) >= k2
        return jax.lax.cond(do2, lambda s: _fold2(s, glob),
                            lambda s: s, state)

    def _core_round_overlap(state: HierFlatState, gk: jax.Array
                            ) -> HierFlatState:
        glob = _core_round_begin(state, gk.shape[0])
        state, _ = jax.lax.scan(lambda s, g: (_core_local(s, g), None),
                                state, gk)
        return _core_round_end_overlap(state, glob)

    # --------------------------------------------- membership repair
    def _core_set_membership(state: HierFlatState, new_active: jax.Array
                             ) -> HierFlatState:
        """Two-level twin of the flat repair: Δ1 recentred per pod over
        that pod's continuing members (Σ_d Δ1 = 0 within every pod with
        survivors), Δ2 recentred over the pods that stay alive
        (Σ_p Δ2 = 0 over the new alive set); dropped/rejoining workers —
        and fully-replaced pods' per-pod buffers — re-seeded from the
        continuing consensus x̂."""
        def _data_sum(x):                      # (P_l, D_l, ...) → (P_l, 1, ...)
            s = jnp.sum(x, axis=1, keepdims=True)
            if data_axis is not None:
                s = jax.lax.psum(s, data_axis)
            return s

        def _pod_sum(x):                       # data-replicated (P_l, 1, ...)
            s = jnp.sum(x, axis=(0, 1))
            if pod_axis is not None:
                s = jax.lax.psum(s, pod_axis)
            return s

        def _all_sum(x):                       # raw (P_l, D_l, ...) → global
            s = jnp.sum(x, axis=(0, 1))
            axes = tuple(a for a in (pod_axis, data_axis) if a is not None)
            if axes:
                s = jax.lax.psum(s, axes)
            return s

        old = state.member.active                      # (P_l, D_l, 1, 1)
        cont = old * new_active
        keep = cont > 0
        n_cont = jnp.maximum(jnp.sum(_all_sum(cont)), 1.0)
        n_cont_pod = _data_sum(cont)                   # (P_l, 1, 1, 1)
        pod_keep = n_cont_pod > 0
        n_new_pod = _data_sum(new_active)
        xhat = _all_sum(jnp.where(keep, state.params.astype(jnp.float32),
                                  0.0)) / n_cont       # (R, C)
        params = jnp.where(keep, state.params,
                           xhat.astype(state.params.dtype)[None, None])
        s1 = _data_sum(jnp.where(keep, state.delta1, 0)
                       ) / jnp.maximum(n_cont_pod, 1.0)
        delta1 = jnp.where(keep, state.delta1 - s1.astype(state.delta1.dtype),
                           jnp.zeros((), state.delta1.dtype))
        n_pods_cont = jnp.maximum(
            jnp.sum(_pod_sum(pod_keep.astype(jnp.float32))), 1.0)
        s2 = _pod_sum(jnp.where(pod_keep, state.delta2, 0)) / n_pods_cont
        delta2 = jnp.where(pod_keep,
                           state.delta2 - s2.astype(state.delta2.dtype
                                                    )[None, None],
                           jnp.zeros((), state.delta2.dtype))
        inner = jax.tree.map(
            lambda x: (jnp.where(keep, x, jnp.zeros((), x.dtype))
                       if getattr(x, "ndim", 0) == 4 else x), state.inner)
        comm = state.comm
        if isinstance(comm, HierCommState):
            have = lambda x: not isinstance(x, tuple)
            comm = HierCommState(
                resid1=(jnp.where(keep, comm.resid1, 0.0)
                        if have(comm.resid1) else ()),
                # a fully-replaced pod's shared intra-pod reference is
                # re-anchored to x̂ (its new members all start there)
                ref1=(jnp.where(pod_keep, comm.ref1, xhat[None, None])
                      if have(comm.ref1) else ()),
                resid2=(jnp.where(pod_keep, comm.resid2, 0.0)
                        if have(comm.resid2) else ()),
                ref2=comm.ref2)
        ov = state.overlap
        if isinstance(ov, OverlapState):
            ov = OverlapState(
                pend=jnp.where(pod_keep, ov.pend, xhat[None, None]),
                pend_k=jnp.where(pod_keep, ov.pend_k, 1.0))
        member = MemberState(
            active=new_active,
            n_active=jnp.sum(_pod_sum((n_new_pod > 0).astype(jnp.float32))),
            n_pod=n_new_pod)
        return state._replace(params=params, delta1=delta1, delta2=delta2,
                              inner=inner, comm=comm, overlap=ov,
                              member=member)

    # --------------------------------------------------------- diagnostics
    # Static key set — shard_map out_specs must be a statically-known
    # pytree (see the flat engine's twin).
    ef_on = comp1 is not None and bool(getattr(comp1, "error_feedback",
                                               False))
    diag_keys = ["params_rms", "drift_sq_mean", "drift_max",
                 "nonfinite_workers", "delta1_residual", "delta2_residual",
                 "zeta_sq_proxy"]
    if ef_on:
        diag_keys += ["ef_resid_rms"]
    if kind != "sgd":
        diag_keys += ["mu_rms"]
    if kind == "adam":
        diag_keys += ["nu_rms"]
    d_red = tuple(a for a in (pod_axis, data_axis, shard_axis)
                  if a is not None)

    def _core_diag_hier(state: HierFlatState) -> dict:
        """Two-level twin of the flat ``_core_diagnostics`` (same
        read-only / own-jit contract).  The invariant residuals follow
        the two-level structure: ``delta1_residual`` is the worst pod's
        ‖mean over its active members of Δ1‖∞ (Σ_d Δ1[p] = 0 within
        every pod), ``delta2_residual`` is ‖mean over alive pods of
        Δ2‖∞ (Σ_p Δ2 = 0); the ζ² proxy is the dispersion of the
        worker's TOTAL correction Δ1 + Δ2 — the quantity that tracks
        ∇Fᵢ − ∇F in the analysis."""
        member = state.member
        masked = isinstance(member, MemberState)
        worker_axes_ = tuple(a for a in (pod_axis, data_axis)
                             if a is not None)

        def keep(buf):                      # (P, D, ...) worker mask
            if not masked:
                return buf.astype(jnp.float32)
            return jnp.where(member.active > 0, buf.astype(jnp.float32),
                             0.0)

        def keep_pod(buf):                  # (P, 1, ...) alive-pod mask
            if not masked:
                return buf.astype(jnp.float32)
            return jnp.where(member.n_pod > 0, buf.astype(jnp.float32),
                             0.0)

        def _gsum(x):
            s = jnp.sum(x)
            return jax.lax.psum(s, d_red) if d_red else s

        def _gmax(x):
            m = jnp.max(x)
            return jax.lax.pmax(m, d_red) if d_red else m

        def _per_worker(x):                 # (P_l, D_l, R_l, C) -> (P_l, D_l)
            s = jnp.sum(x, axis=(2, 3))
            if shard_axis is not None:
                s = jax.lax.psum(s, shard_axis)
            return s

        def _grid_sum(x):                   # worker-axes sum -> (R_l, C)
            s = jnp.sum(x, axis=(0, 1))
            return jax.lax.psum(s, worker_axes_) if worker_axes_ else s

        def _wscalar(s):
            return (jax.lax.psum(s, worker_axes_) if worker_axes_ else s)

        def _data_sum(x):                   # (P_l, D_l, ...) -> (P_l, 1, ...)
            s = jnp.sum(x, axis=1, keepdims=True)
            if data_axis is not None:
                s = jax.lax.psum(s, data_axis)
            return s

        def _pod_sum(x):                    # data-replicated (P_l, 1, ...)
            s = jnp.sum(x, axis=(0, 1))
            if pod_axis is not None:
                s = jax.lax.psum(s, pod_axis)
            return s

        if masked:
            npod = jnp.sum(member.n_pod)
            if pod_axis is not None:
                npod = jax.lax.psum(npod, pod_axis)
            n = jnp.maximum(npod, 1.0)      # total ACTIVE workers
        else:
            n = float(p_total * d_total)
        elems = float(fspec.rows * fspec.lanes)

        out = {}
        p32 = keep(state.params)
        bad = _per_worker((~jnp.isfinite(p32)).astype(jnp.float32))
        out["nonfinite_workers"] = _wscalar(
            jnp.sum((bad > 0).astype(jnp.float32)))
        out["params_rms"] = jnp.sqrt(_gsum(p32 * p32) / (n * elems))
        xhat = _grid_sum(p32) * (1.0 / n)
        dev = keep(p32 - xhat[None, None])
        drift_w = _per_worker(dev * dev)
        out["drift_sq_mean"] = _wscalar(jnp.sum(drift_w)) * (1.0 / n)
        out["drift_max"] = jnp.sqrt(_gmax(drift_w))

        d1 = keep(state.delta1)
        s1 = _data_sum(d1)                  # (P_l, 1, R_l, C)
        mean1 = (s1 * (1.0 / jnp.maximum(member.n_pod, 1.0)) if masked
                 else s1 / float(d_total))
        out["delta1_residual"] = _gmax(jnp.abs(mean1))
        d2 = keep_pod(state.delta2)
        s2 = _pod_sum(d2)                   # (R_l, C)
        mean2 = (s2 * (1.0 / member.n_active) if masked
                 else s2 / float(p_total))
        out["delta2_residual"] = _gmax(jnp.abs(mean2))
        c = keep(d1 + d2.astype(jnp.float32))   # total correction per worker
        cmean = _grid_sum(c) * (1.0 / n)
        cdev = keep(c - cmean[None, None])
        out["zeta_sq_proxy"] = _gsum(cdev * cdev) * (1.0 / n)

        if ef_on:
            r32 = keep(state.comm.resid1)
            out["ef_resid_rms"] = jnp.sqrt(_gsum(r32 * r32) / (n * elems))
        if kind != "sgd":
            m32 = keep(state.inner if kind == "momentum"
                       else state.inner.mu)
            out["mu_rms"] = jnp.sqrt(_gsum(m32 * m32) / (n * elems))
        if kind == "adam":
            if sm3:
                row32 = keep(state.inner.nu.row)
                col32 = keep(state.inner.nu.col)
                cnt = n * float(fspec.rows + fspec.shards * fspec.lanes)
                out["nu_rms"] = jnp.sqrt((_gsum(row32 * row32)
                                          + _gsum(col32 * col32)) / cnt)
            else:
                n32 = keep(state.inner.nu)
                out["nu_rms"] = jnp.sqrt(_gsum(n32 * n32) / (n * elems))
        return {k: out[k] for k in diag_keys}

    # ----------------------------------------------------- shard_map wrap
    meshless = mesh is None or (pod_axis is None and data_axis is None
                                and shard_axis is None)

    def _specs(state):
        return _hier_pspecs(state, pod_axis, data_axis,
                            shard_axis=shard_axis, shards=cfg.engine.shards)

    def _sharded(fn, gspec: Optional[P] = None):
        if meshless:
            return fn

        def wrapped(state, *rest):
            sspec = _specs(state)
            in_specs = (sspec,) if gspec is None else (sspec, gspec)
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=sspec,
                                 check_vma=False)(state, *rest)

        return wrapped

    gspec = P(pod_axis, data_axis, shard_axis, None)
    local_core = _sharded(_core_local, gspec=gspec)
    train_core = _sharded(_core_train, gspec=gspec)
    sync_core = _sharded(_core_sync)
    sync1_core = _sharded(_core_sync1)
    sync2_core = _sharded(_core_sync2)
    round_core = _sharded(_core_round_overlap if cfg.overlap
                          else _core_round,
                          gspec=P(None, pod_axis, data_axis, shard_axis,
                                  None))
    round_end_core = _sharded(_core_round_end)

    def diagnostics(state: HierFlatState) -> dict:
        """One read-only jitted pass of two-level algorithm-health
        scalars (see ``_core_diag_hier``).  Jit WITHOUT donation."""
        if meshless:
            return _core_diag_hier(state)
        out_specs = {k: P() for k in diag_keys}
        return jax.shard_map(_core_diag_hier, mesh=mesh,
                             in_specs=(_specs(state),),
                             out_specs=out_specs,
                             check_vma=False)(state)

    round_begin = round_fold = None
    if cfg.overlap:
        def round_begin(state, k: int):
            """The round-START level-2 collective (zeros off the k2
            cadence); ``k`` is this round's length, needed to decide the
            cadence before the scan advances ``step``."""
            _check_round()
            if meshless:
                return _core_round_begin(state, k)
            sspec = _specs(state)
            return jax.shard_map(
                functools.partial(_core_round_begin, k=k), mesh=mesh,
                in_specs=(sspec,), out_specs=P(shard_axis, None),
                check_vma=False)(state)

        def round_fold(state, glob):
            """Blocking sync1 + (on the k2 cadence) the stale level-2
            fold of ``round_begin``'s result."""
            _check_round()
            if meshless:
                return _core_round_end_overlap(state, glob)
            sspec = _specs(state)
            return jax.shard_map(
                _core_round_end_overlap, mesh=mesh,
                in_specs=(sspec, P(shard_axis, None)), out_specs=sspec,
                check_vma=False)(state, glob)

    set_membership = None
    if member_on:
        member_core = _sharded(_core_set_membership,
                               gspec=P(pod_axis, data_axis, None, None))

        def set_membership(state: HierFlatState, active) -> HierFlatState:
            """Change the active set to ``active`` ((W,) or (P, D)
            bools/floats, pod-major), repairing the two-level invariants.
            Call between rounds (jit with donate_argnums=(0,))."""
            m = jnp.asarray(active, jnp.float32).reshape(
                p_total, d_total)[:, :, None, None]
            return member_core(state, m)

    # --------------------------------------------------------- public API
    def _gbuf(grads: Any) -> jax.Array:
        return flat.flatten_grid(fspec, grads, dtype=fspec.dtype)

    def local_step(state, grads):
        return local_core(state, _gbuf(grads))

    def train_step(state, grads):
        return train_core(state, _gbuf(grads))

    def _check_round():
        if k2 % k1:
            raise ValueError(
                f"round execution treats one k1 period as the unit and "
                f"nests the level-2 cadence, which needs k2 % k1 == 0; "
                f"got k1={k1}, k2={k2}")

    def round_step(state, grads_k):
        """One k1 round: scan k1 local steps + sync1 (+ sync2 when the k2
        cadence is due).  ``grads_k``: grid-stacked grads pytree with an
        extra leading step axis ((k1, P, D, ...) leaves)."""
        _check_round()
        gk = jax.vmap(
            lambda t: flat.flatten_grid(fspec, t, dtype=fspec.dtype)
        )(grads_k)
        return round_core(state, gk)

    def round_step_flat(state, gk):
        """``round_step`` over an already-flattened (k1, P, D, R, C)
        grads buffer — no pytree-flatten pass."""
        _check_round()
        return round_core(state, gk)

    def round_end(state):
        _check_round()
        return round_end_core(state)

    def params_tree(state):
        """Grid-stacked parameter pytree view ((P, D, ...) leaves)."""
        return flat.unflatten_grid(fspec, state.params)

    def avg_model(state):
        if isinstance(state.member, MemberState):
            m = state.member.active
            s = jnp.sum(jnp.where(m > 0, state.params, 0), axis=(0, 1))
            return flat.unflatten_tree(
                fspec, s * (1.0 / jnp.maximum(jnp.sum(m), 1.0)))
        return flat.unflatten_tree(fspec,
                                   jnp.mean(state.params, axis=(0, 1)))

    return Engine(algorithm=cfg.algorithm, spec=fspec, algo=algo,
                  init=init if meshless else _placed(init, mesh, _specs),
                  train_step=train_step, local_step=local_step,
                  sync=lambda s: sync_core(s), average_model=avg_model,
                  params_tree=params_tree,
                  sync1=lambda s: sync1_core(s),
                  sync2=lambda s: sync2_core(s),
                  grid=(p_total, d_total),
                  round_step=round_step, round_end=round_end,
                  round_step_flat=round_step_flat,
                  round_begin=round_begin, round_fold=round_fold,
                  backend=backend, interpret=interpret,
                  compressors=(comp1, comp2),
                  set_membership=set_membership,
                  diagnostics=diagnostics)
