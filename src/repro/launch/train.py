"""Training driver (real execution, on the host CPU or a TPU).

Runs VRL-SGD (or a baseline, or two-level hierarchical VRL-SGD) on a
selectable architecture's reduced (``--smoke``) or full config with the
synthetic non-iid LM pipeline, periodic checkpointing, and average-model
evaluation — the same code path the dry-run lowers for the production mesh.
On a TPU, ``chip_smoke.py`` at the repo root runs this driver at published
widths.

Execution is ROUND-based by default (``EngineConfig.round_scan``): each
communication period runs as ONE jit dispatch (k scanned local steps +
sync, state donated), tokens are prefetched per round, and losses stay
device-side until a logging boundary — ``--log-every`` counts rounds.
``--no-round`` falls back to one dispatch per local step (and per-step
loss fetch), which is the old behaviour.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --workers 4 --steps 50 --k 10 --algorithm vrl_sgd

Hierarchical on a placeholder pod grid (devices permitting, ``--mesh-grid``
shard_maps the pod-major worker grid so level-1 syncs all-reduce only the
intra-pod axis and level-2 only the cross-pod axis):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --workers 8 --pods 2 --algorithm hier_vrl_sgd --k1 2 --k2 8 \
      --mesh-grid

Fault tolerance (elastic rounds): ``--faults`` replays a deterministic
chaos schedule (gradient NaN/Inf, worker crash/rejoin, simulated mid-save
kill), ``--membership`` threads the survivor mask through every sync (the
repair keeps Σ_i Δ_i = 0 exactly), ``--guard`` checks finiteness each
round and rolls back to the last good checkpoint (or the round-start
snapshot) with bounded retries, and ``--resume auto`` restarts from the
newest complete checkpoint — resharding the worker axis if ``--workers``
changed:

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --workers 4 --steps 40 --k 5 --membership --guard \
      --faults "nan@1:12,crash@1:15,rejoin@1:30" \
      --ckpt /tmp/run --ckpt-every 10 --resume auto

Partial participation (federated client sampling): ``--clients M`` keeps M
logical clients' engine state (Dirichlet non-IID data each) in a host-side
store; every round a seed-deterministic cohort of ``--workers`` clients is
gathered into the flat buffers (one contiguous copy per buffer), Σ Δ is
recentred over the cohort, the UNCHANGED compiled round runs (still one
sync all-reduce), and the rows scatter back.  M == --workers with
``--participation 1.0`` is bitwise the plain engine path:

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --workers 8 --steps 40 --k 5 --clients 32 --participation 0.25 \
      --alpha 0.1

Observability (structured telemetry, ``repro.obs``): ``--metrics
out.jsonl`` streams schema-versioned JSONL events — a ``run_start``
header with the full run description (including the measured sync wire
bytes), then per-round ``round``/``sync`` records, ``diag``
algorithm-health records at ``--log-every`` cadence (drift dispersion,
the Δ-dispersion ζ² proxy for the paper's inter-worker gradient
variance, Σ Δ / Σ B invariant residuals, EF-residual and moment norms,
non-finite worker count), ``membership`` / ``rollback`` / ``cohort`` /
``checkpoint`` / ``restore`` / ``fault`` events as they happen, and a
``run_end`` record with the final averaged-model loss, the compiled
round's attention executor (``flash``/``dense`` and its kernel count)
and wall-clock phase-timer p50/p95s (the phases are the host-visible
boundaries — data staging, the round dispatch+block, eval, diag,
gather/scatter, checkpoint; local-steps/sync/fold live inside ONE
compiled dispatch, which only the device trace's named scopes split).
Each phase is also a profiler host span (``obs.timers.phase``) and each
round runs under a
``StepTraceAnnotation``, so a ``--profile-round`` trace shows the
training loop beside the device ops.  The diagnostics pass is one read-only
jit over the flat engine state, SEPARATE from the compiled round — the
one-sync-all-reduce HLO contract is untouched.  ``--invariant-alarm
1e-3`` feeds a tripped Σ Δ / Σ B residual into the ``--guard``
rollback; ``--profile-round N --profile-dir d`` captures a
jax.profiler trace around round N.  Render a stream (or diff two) with
``scripts/report.py``:

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --workers 4 --steps 40 --k 5 --metrics run.jsonl --diag \
      --guard --invariant-alarm 1e-3 --ckpt /tmp/run
  python scripts/report.py run.jsonl
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.comm import compressors as comm_mod
from repro.configs import registry
from repro.configs.base import EngineConfig, HierConfig, VRLConfig
from repro.core import clients as clients_mod
from repro.core import engine as engine_mod
from repro.core import schedule as schedule_mod
from repro.data import assigned_token_stream
from repro.data import partition as partition_mod
from repro.fault import FaultSchedule
from repro.launch import mesh as mesh_mod
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as T
from repro.obs import diagnostics as obs_diag
from repro.obs import metrics as obs_metrics
from repro.obs import scopemap
from repro.obs import timers as timers_mod
from repro.train.loss import cross_entropy_lm
from repro.train.train_loop import make_train_step


# --guard's loss-trend trip-wire: a round whose mean loss exceeds
# factor * last_good + slack is treated as diverged even though every
# value is finite (the signature of a scale-poisoned gradient).  The
# slack keeps ordinary early-training noise from tripping it.
_BLOWUP_FACTOR = 10.0
_BLOWUP_SLACK = 1.0


def _validate_args(args) -> None:
    """Early, named range checks — a bad flag should fail before the
    model compiles, not as an inscrutable shape error mid-run."""
    if not (0.0 <= args.deadline <= 1.0):
        raise SystemExit(f"--deadline is a probability in [0, 1], got "
                         f"{args.deadline}")
    if args.ckpt_every <= 0:
        raise SystemExit(f"--ckpt-every must be a positive step count, "
                         f"got {args.ckpt_every}")
    if args.shards < 1:
        raise SystemExit(f"--shards must be >= 1, got {args.shards}")
    if args.steps < 1:
        raise SystemExit(f"--steps must be >= 1, got {args.steps}")
    if args.k < 1:
        raise SystemExit(f"--k must be >= 1, got {args.k}")
    if args.workers < 1:
        raise SystemExit(f"--workers must be >= 1, got {args.workers}")
    if args.ckpt_retain < 0:
        raise SystemExit(f"--ckpt-retain must be >= 0 (0 keeps all), got "
                         f"{args.ckpt_retain}")
    if args.max_retries < 0:
        raise SystemExit(f"--max-retries must be >= 0, got "
                         f"{args.max_retries}")
    if args.clients < 0:
        raise SystemExit(f"--clients must be >= 0 (0 = no client "
                         f"sampling), got {args.clients}")
    if args.clients and args.clients < args.workers:
        raise SystemExit(f"--clients {args.clients} must be >= --workers "
                         f"{args.workers} (the cohort size is the worker "
                         f"count)")
    if args.participation and not args.clients:
        raise SystemExit("--participation needs --clients (it is the "
                         "sampled fraction of the client population)")
    if args.participation and not (0.0 < args.participation <= 1.0):
        raise SystemExit(f"--participation is a fraction in (0, 1], got "
                         f"{args.participation}")
    if args.participation:
        cohort = round(args.participation * args.clients)
        if cohort != args.workers:
            raise SystemExit(
                f"--participation {args.participation} of "
                f"{args.clients} clients is a cohort of {cohort}, but "
                f"--workers is {args.workers} — set --workers {cohort} "
                f"(the cohort size is the worker count)")
    if args.invariant_alarm < 0:
        raise SystemExit(f"--invariant-alarm must be >= 0 (0 disables "
                         f"the residual alarm), got {args.invariant_alarm}")
    if args.profile_round < 0:
        raise SystemExit(f"--profile-round counts rounds from 1 (0 = "
                         f"off), got {args.profile_round}")
    if args.profile_round and not args.profile_dir:
        raise SystemExit("--profile-round needs --profile-dir (where the "
                         "jax.profiler trace lands)")
    if args.profile_round and not args.round:
        raise SystemExit("--profile-round traces a compiled round; drop "
                         "--no-round")


def _worker_devices(params) -> list:
    """Ids of the devices holding each worker's rows of a flat params
    buffer ((W, R, C), or pod-major (P, D, R, C) flattened), so a run
    records where its workers really live."""
    lead = params.shape[:-2]
    ids = np.arange(math.prod(lead)).reshape(lead)
    held = [set() for _ in range(ids.size)]
    for dev, idx in params.sharding.devices_indices_map(
            params.shape).items():
        for w in ids[tuple(idx[:len(lead)])].ravel():
            held[w].add(dev.id)
    return [sorted(h) for h in held]


def _build_faults(args) -> FaultSchedule | None:
    if not args.faults:
        return None
    if not args.round:
        raise SystemExit("--faults injects per-round; drop --no-round")
    try:
        if args.faults == "random":
            fs = FaultSchedule.random(
                args.steps, args.workers,
                seed=args.fault_seed if args.fault_seed is not None
                else args.seed,
                killsave=bool(args.ckpt))
        else:
            fs = FaultSchedule.parse(args.faults)
    except ValueError as e:
        raise SystemExit(f"--faults: {e}")
    for e in fs.events:
        if e.worker >= args.workers:
            raise SystemExit(f"--faults: event {e.kind}@{e.worker}:"
                             f"{e.step} targets a worker >= --workers "
                             f"{args.workers}")
    for e in fs.membership_events():
        if fs.active_at(e.step, args.workers).sum() < 1:
            raise SystemExit(f"--faults: schedule leaves no active worker "
                             f"at step {e.step}")
    return fs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b",
                    choices=registry.list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--algorithm", default="vrl_sgd",
                    choices=sorted(engine_mod.ALGO_SPECS))
    ap.add_argument("--comm-schedule", default=None,
                    help="stagewise round schedule: const | "
                         "stagewise[:k0:rounds:k_max] | custom:1x4,2x4,8x2 "
                         "(default: constant --k; stl_sgd defaults to the "
                         "doubling ramp 1 -> --k).  Each distinct stage k "
                         "compiles one round executable (RoundCache).")
    ap.add_argument("--bvr-beta", type=float, default=0.5,
                    help="bvr_l_sgd bias-variate EMA rate (0 = plain "
                         "vrl_sgd)")
    ap.add_argument("--compress", default=None,
                    help="sync-payload compressor: none | int8 | "
                         "topk[:rate] (append :noef to drop error "
                         "feedback).  none/rate-1 is bitwise the "
                         "uncompressed path")
    ap.add_argument("--compress2", default=None,
                    help="hier_vrl_sgd: override the cross-pod sync2 "
                         "compressor (default: --compress) so the slow "
                         "DCI tier compresses harder")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "fused", "xla", "reference"],
                    help="update math: auto (Pallas where it compiles, "
                         "XLA elsewhere), fused Pallas, plain-jnp xla, or "
                         "the per-leaf reference path")
    ap.add_argument("--block", type=int, default=0,
                    help="engine Pallas tile height (0 = auto)")
    ap.add_argument("--shards", type=int, default=1,
                    help="row-block-shard every engine buffer over a model "
                         "mesh axis: per-device engine HBM drops by this "
                         "factor and the sync stays ONE (per-shard) all-"
                         "reduce.  With --mesh-grid the mesh grows a "
                         "trailing 'shard' axis (needs workers*shards "
                         "devices); without it the layout pads rows to "
                         "shard boundaries but runs replicated.  1 = "
                         "bitwise the unsharded path")
    ap.add_argument("--moment-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="storage dtype for inner-optimizer moment buffers "
                         "(math stays fp32 in-register); bfloat16 halves "
                         "moment HBM")
    ap.add_argument("--sm3", action="store_true",
                    help="SM3-factored adam second moment: nu's (W, R, C) "
                         "buffer becomes row (W, R, 1) + lane (W, S, C) "
                         "stats — ~lanes-fold less second-moment HBM "
                         "(adam only)")
    ap.add_argument("--no-round", dest="round", action="store_false",
                    default=True,
                    help="dispatch every local step from python instead of "
                         "compiling one scan-fused round per comm period")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped rounds: issue the sync all-reduce at "
                         "round START over the previous boundary's "
                         "transmitted positions, so it runs concurrently "
                         "with the round's local steps and the one-round-"
                         "stale mean is folded in at the end (hier: "
                         "overlaps the cross-pod sync2 only).  Needs round "
                         "execution and an engine backend.")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="straggler deadline: per-round probability in "
                         "[0, 1] that a participant misses its capture "
                         "(simulated), keeps its last transmitted position "
                         "and — under compressed sync — parks the missed "
                         "payload in its EF residual.  Requires --overlap.")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--clients", type=int, default=0,
                    help="partial participation: keep this many LOGICAL "
                         "clients' engine state (params drift, Δ, bias, "
                         "EF residual, moments — each on its own "
                         "Dirichlet non-iid data shard) in a host-side "
                         "store, and sample a cohort of --workers of "
                         "them per round into the flat buffers.  The "
                         "compiled round is unchanged (one sync all-"
                         "reduce); Σ Δ is recentred over each sampled "
                         "cohort.  0 = off; --clients == --workers is "
                         "bitwise the plain path")
    ap.add_argument("--participation", type=float, default=0.0,
                    help="sampled fraction of --clients per round, as a "
                         "cross-check: round(participation * clients) "
                         "must equal --workers (the cohort size).  "
                         "Default: --workers / --clients")
    ap.add_argument("--batch", type=int, default=8, help="per-worker batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--k", type=int, default=10, help="communication period")
    ap.add_argument("--pods", type=int, default=2,
                    help="hier_vrl_sgd: pods P (workers split as P x W/P)")
    ap.add_argument("--k1", type=int, default=0,
                    help="hier_vrl_sgd intra-pod period (default: --k)")
    ap.add_argument("--k2", type=int, default=0,
                    help="hier_vrl_sgd cross-pod period (default: 4*k1)")
    ap.add_argument("--mesh-grid", action="store_true",
                    help="build a (pods, W/pods) device mesh with axes "
                         "(pod, data) and shard the worker grid over it")
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--warmup", action="store_true")
    ap.add_argument("--alpha", type=float, default=0.05,
                    help="Dirichlet non-iid skew (lower = more skewed)")
    ap.add_argument("--identical", action="store_true")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint ROOT dir: saves land in per-step "
                         "ckpt-XXXXXXXX/ subdirs with an atomic 'latest' "
                         "pointer (each save is temp-file + rename, so a "
                         "kill mid-save never tears a checkpoint)")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-retain", type=int, default=3,
                    help="keep only the newest N step checkpoints "
                         "(0 = keep all)")
    ap.add_argument("--resume", default=None,
                    help="'auto' resumes from the newest complete "
                         "checkpoint under --ckpt (fresh start if none); "
                         "a path resumes from that step dir.  If "
                         "--workers differs from the save, the flat state "
                         "is RESHARDED (rows tiled, Δ recentred to Σ=0, "
                         "EF residuals dropped); layout/compressor/moment "
                         "mismatches still fail loudly")
    ap.add_argument("--faults", default=None,
                    help="deterministic chaos schedule: 'kind@worker:step' "
                         "events joined by commas — nan/inf (gradient "
                         "poison), scale@w:step:mult (finite gradient "
                         "blow-up — silent corruption only the --guard "
                         "loss trend catches), crash/rejoin (membership), "
                         "killsave:step (die inside the next checkpoint "
                         "save).  'random' draws a schedule from "
                         "--fault-seed.  Example: "
                         "'nan@1:12,scale@0:20:1e3,crash@1:15,"
                         "rejoin@1:30,killsave:20'")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="seed for --faults random (default: --seed)")
    ap.add_argument("--membership", action="store_true",
                    help="elastic membership: thread an active-worker "
                         "mask through every sync (masked means stay ONE "
                         "all-reduce; Σ Δ = 0 is repaired exactly on every "
                         "drop/rejoin; full mask is bitwise the plain "
                         "path).  Auto-enabled by crash/rejoin faults.")
    ap.add_argument("--guard", action="store_true",
                    help="divergence guard: check loss/param finiteness "
                         "AND the loss trend (a round whose mean loss "
                         "blows past 10x the last good round + 1 is "
                         "diverged even when finite — the scale-poison "
                         "signature) each round; on failure roll back to "
                         "the last good checkpoint (or the round-start "
                         "snapshot) and retry with backoff, bounded by "
                         "--max-retries")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="divergence-guard rollback budget")
    ap.add_argument("--loss-out", default=None,
                    help="write final {steps, final_loss, avg_model_loss} "
                         "json here (chaos CI compares runs with it)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics", default=None,
                    help="stream schema-versioned JSONL telemetry here "
                         "(repro.obs): a run_start meta header, then "
                         "round/sync/diag/eval/membership/rollback/"
                         "cohort/checkpoint/restore/fault/run_end "
                         "records, one object per line, flushed per "
                         "event so crashed runs leave a valid prefix.  "
                         "Summarize (or diff two) with scripts/report.py")
    ap.add_argument("--diag", action="store_true",
                    help="print the engine's algorithm-health "
                         "diagnostics at --log-every cadence: drift "
                         "dispersion, the Δ-dispersion ζ² proxy, "
                         "Σ Δ / Σ B invariant residuals, EF/moment "
                         "norms, non-finite worker count.  One "
                         "read-only jit over the flat state — the "
                         "compiled round keeps its single all-reduce.  "
                         "--metrics records the same fields without "
                         "this flag's console lines")
    ap.add_argument("--invariant-alarm", type=float, default=0.0,
                    help="alarm threshold on the Σ Δ / Σ B invariant "
                         "residuals (0 = off).  With --guard a tripped "
                         "alarm is a divergence (rollback + retry); "
                         "without it the alarm prints and the run "
                         "continues.  Under a lossy --compress the "
                         "residual is genuinely nonzero (EF-bounded "
                         "bias) — leave off or set above that floor")
    ap.add_argument("--profile-round", type=int, default=0,
                    help="capture a jax.profiler trace around the Nth "
                         "compiled round (1-based; 0 = off)")
    ap.add_argument("--profile-dir", default=None,
                    help="directory for the --profile-round trace")
    args = ap.parse_args(argv)
    _validate_args(args)
    enable_compile_cache()

    cfg = (registry.smoke_arch(args.arch) if args.smoke
           else registry.get_arch(args.arch))
    print(f"arch: {registry.describe(args.arch)}"
          f"{' [reduced smoke variant]' if args.smoke else ''}")
    hier = None
    if args.algorithm == "hier_vrl_sgd":
        if args.workers % args.pods:
            raise SystemExit(f"--workers {args.workers} not divisible by "
                             f"--pods {args.pods}")
        k1 = args.k1 or args.k
        k2 = args.k2 or 4 * k1
        hier = HierConfig(k1=k1, k2=k2,
                          grid=(args.pods, args.workers // args.pods))
        print(f"hier: {hier.grid[0]} pods x {hier.grid[1]} workers, "
              f"k1={k1} (intra-pod), k2={k2} (cross-pod)")
    sched_arg = (schedule_mod.parse_schedule(args.comm_schedule, args.k)
                 if args.comm_schedule else None)
    if hier is not None and sched_arg is not None:
        raise SystemExit("--comm-schedule drives the flat algorithms; "
                         "hier_vrl_sgd's cadence is --k1/--k2")
    comp_arg = (comm_mod.parse_compressor(args.compress)
                if args.compress else None)
    comp2_arg = (comm_mod.parse_compressor(args.compress2)
                 if args.compress2 else None)
    if comp2_arg is not None and args.algorithm != "hier_vrl_sgd":
        raise SystemExit("--compress2 drives the hierarchical cross-pod "
                         "sync2; flat algorithms have one level "
                         "(--compress)")
    if args.overlap and not args.round:
        raise SystemExit("--overlap hides the sync behind the next round's "
                         "local steps, which needs round execution; drop "
                         "--no-round")
    if args.clients:
        if args.algorithm == "hier_vrl_sgd":
            raise SystemExit("--clients samples cohorts into the flat "
                             "(W, R, C) buffers; hier_vrl_sgd runs a "
                             "pod-major grid — drop --clients or the "
                             "hierarchy")
        if args.overlap:
            raise SystemExit("--clients does not compose with --overlap: "
                             "the overlapped pend buffer is one round "
                             "stale and would mix positions from "
                             "different clients across cohorts")
        if not args.round:
            raise SystemExit("--clients gathers/scatters per round; drop "
                             "--no-round")
        if args.backend == "reference":
            raise SystemExit("--clients needs the flat-buffer engine's "
                             "contiguous client store; --backend "
                             "reference has none")
    faults = _build_faults(args)
    membership = args.membership
    if faults is not None and faults.membership_events() and not membership:
        print("faults: schedule has crash/rejoin events — enabling "
              "--membership")
        membership = True
    if membership and args.backend == "reference":
        raise SystemExit("--membership needs the flat-buffer engine's "
                         "MemberState; --backend reference has none")
    if faults is not None:
        print(f"faults: {faults.describe()}")
    vrl = VRLConfig(algorithm=args.algorithm, comm_period=args.k,
                    learning_rate=args.lr, warmup=args.warmup,
                    update_backend=args.backend, bvr_beta=args.bvr_beta,
                    comm_schedule=sched_arg, compress=comp_arg,
                    compress2=comp2_arg, overlap=args.overlap,
                    deadline=args.deadline, membership=membership,
                    moment_dtype=args.moment_dtype, sm3=args.sm3,
                    engine=EngineConfig(block=args.block,
                                        round_scan=args.round,
                                        shards=args.shards), hier=hier)
    sched = engine_mod.comm_schedule(vrl)    # explicit or the algo default
    if sched is not None:
        print(f"comm schedule: stages {sched.stages} (k repeats from the "
              f"last stage; {len(sched.distinct_periods())} distinct round "
              f"lengths)")
    mesh = None
    worker_axes = ("data",)
    if args.mesh_grid:
        try:
            mesh = mesh_mod.make_engine_mesh(
                args.workers, shards=args.shards,
                pods=hier.grid[0] if hier else 0,
                shard_axis=vrl.engine.shard_axis)
        except ValueError as e:
            raise SystemExit(f"--mesh-grid: {e}")
        worker_axes = ("pod", "data")
        print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    try:
        bundle = make_train_step(cfg, vrl, remat=not args.smoke, mesh=mesh,
                                 worker_axes=worker_axes)
    except ValueError as e:
        raise SystemExit(str(e))
    state = bundle.init_state(jax.random.PRNGKey(args.seed), args.workers)
    store = None
    if args.clients:
        try:
            store = clients_mod.ClientStore(state, args.clients)
        except ValueError as e:
            raise SystemExit(f"--clients: {e}")
        print(f"clients: {args.clients} logical clients over "
              f"{args.workers} worker slots (participation "
              f"{args.workers / args.clients:.3g}), host store "
              f"{store.nbytes / 2**20:.1f} MiB")
    n_params = (bundle.engine.spec.size if bundle.engine is not None else
                sum(p.size for p in jax.tree.leaves(state.params))
                // args.workers)
    resolved = engine_mod.resolve_backend(vrl)
    print(f"params: {n_params/1e6:.2f}M x {args.workers} workers, "
          f"algorithm={args.algorithm}, k={args.k}, "
          f"backend={args.backend}"
          + (f" -> {resolved}" if resolved != args.backend else "")
          + f", round_scan={args.round}")
    if bundle.engine is not None:
        es = bundle.engine.spec
        moments = ("" if args.moment_dtype == "float32" and not args.sm3
                   else f", moments={args.moment_dtype}"
                        + ("+sm3" if args.sm3 else ""))
        shard_note = ""
        if es.shards > 1:
            placed = mesh is not None and vrl.engine.shard_axis in (
                mesh.axis_names if mesh is not None else ())
            shard_note = (f", shards={es.shards}"
                          + ("" if placed else " (layout only — no mesh "
                             "axis; rows pad to shard boundaries)"))
        print(f"engine: flat buffer {es.rows}x{es.lanes} "
              f"({es.padded - es.size} pad elems), block={es.block}"
              f"{shard_note}{moments}")
    if args.overlap:
        print(f"overlap: sync collective issued at round start (one-round-"
              f"stale fold at the boundary"
              + (f"; cross-pod sync2 only, sync1 blocking" if hier else "")
              + (f"), deadline: miss prob {args.deadline}"
                 if args.deadline else ")"))
    comps = (bundle.engine.compressors if bundle.engine is not None
             else comm_mod.resolve_pair(vrl))
    if any(c is not None for c in comps) and bundle.engine is not None:
        es = bundle.engine.spec
        item = jnp.dtype(es.dtype).itemsize
        raw = comm_mod.raw_bytes(es.rows, es.lanes, item)
        distinct = []              # one figure per distinct compressor,
        for c in comps:            # matching describe_pair's collapsing
            if c is not None and c not in distinct:
                distinct.append(c)
        wires = [comm_mod.wire_bytes(c, rows=es.rows, lanes=es.lanes,
                                     size=es.size, itemsize=item)
                 for c in distinct]
        print(f"compress: {comm_mod.describe_pair(comps)} — sync wire "
              + " / ".join(f"{w/2**20:.2f} MiB ({raw/w:.1f}x)"
                           for w in wires)
              + f" vs raw {raw/2**20:.2f} MiB per worker payload")

    # ----------------------------------------------------- observability
    # The structured telemetry channel (repro.obs).  --metrics streams
    # schema-versioned JSONL events; --diag/--invariant-alarm run the
    # engine's READ-ONLY diagnostics pass at --log-every cadence as its
    # own jit — the compiled round and its one-sync-all-reduce HLO are
    # untouched.  Console prints stay the human channel; the stream is
    # the machine channel.
    diag_wanted = args.diag or args.invariant_alarm > 0
    if diag_wanted and bundle.engine is None:
        raise SystemExit("--diag/--invariant-alarm read the flat engine "
                         "state; --backend reference has none")
    wire = obs_diag.wire_bytes_per_sync(bundle.engine)
    mw = obs_metrics.NullWriter()
    if args.metrics:
        mw = obs_metrics.MetricsWriter(args.metrics, run_meta={
            "arch": args.arch, "smoke": bool(args.smoke),
            "algorithm": args.algorithm, "workers": args.workers,
            "clients": args.clients or None, "batch": args.batch,
            "seq": args.seq, "steps": args.steps, "k": args.k,
            "k1": hier.k1 if hier else None,
            "k2": hier.k2 if hier else None,
            "lr": args.lr, "seed": args.seed,
            "backend": args.backend, "resolved_backend": resolved,
            "interpret": (bundle.engine.interpret
                          if bundle.engine is not None else None),
            "device": {"platform": jax.devices()[0].platform,
                       "kind": jax.devices()[0].device_kind,
                       "count": jax.device_count()},
            "round_scan": bool(args.round), "overlap": bool(args.overlap),
            "membership": bool(membership), "guard": bool(args.guard),
            "shards": args.shards,
            "compress": comm_mod.pair_meta(comps),
            "faults": faults.describe() if faults is not None else None,
            "n_params": int(n_params),
            "worker_devices": (_worker_devices(state.params)
                               if bundle.engine is not None else None),
            "wire": wire,
            "client_store": store.meta() if store is not None else None,
        })
        print(f"metrics: streaming JSONL events -> {args.metrics}")
    timers = timers_mod.PhaseTimers() if mw.active else None
    phase = functools.partial(timers_mod.phase, timers=timers)
    diag_fn = None
    if bundle.engine is not None and (diag_wanted or mw.active):
        diag_fn = jax.jit(bundle.engine.diagnostics)
    profiling = profiled = False

    # data assignment: one Dirichlet-skewed shard per unit (logical client
    # or physical worker) to start; a resumed run re-splits the SAVED
    # assignment instead (below), so per-unit distributions survive a
    # resharded resume.  The trivial fresh assignment is bitwise the old
    # lm_token_stream, so non-resumed runs are unchanged.
    units = args.clients if args.clients else args.workers
    assignment = partition_mod.contiguous_assignment(units, units)

    @jax.jit
    def eval_avg(state, toks, labels):
        avg = bundle.average_model(state)
        logits, _ = T.forward(cfg, avg, toks.reshape(-1, args.seq))
        return cross_entropy_lm(logits, labels.reshape(-1, args.seq))

    def save_into(path, t):
        meta = {"step": t, "arch": args.arch, "workers": args.workers,
                "assignment": partition_mod.assignment_to_meta(assignment)}
        if store is not None:
            # client mode checkpoints the STORE (every client's state,
            # (M, ...) leaves + shared globals), not the transient cohort
            # window; the layout/compressor/moment metadata still rides
            # along so mismatched restores fail loudly
            meta["clients"] = args.clients
            meta["flat_spec"] = bundle.engine.spec.meta()
            meta["compressors"] = comm_mod.pair_meta(
                bundle.engine.compressors)
            meta["moments"] = ckpt.moments_meta(vrl)
            ckpt.save(path, store.to_tree(), meta=meta)
        elif bundle.engine is not None:
            ckpt.save_flat_state(
                path, state, bundle.engine.spec, meta=meta,
                grid=bundle.engine.grid,
                compressors=comm_mod.pair_meta(bundle.engine.compressors),
                moments=ckpt.moments_meta(vrl))
        else:
            ckpt.save(path, state, meta=meta)

    def checkpoint(t):
        # simulate a process dying inside the save: the atomic-rename
        # format must leave the previous complete checkpoint in place
        if faults is not None and faults.killsave_at(t):
            try:
                with ckpt.kill_save():
                    ckpt.save_step(args.ckpt, t, lambda p: save_into(p, t),
                                   retain=args.ckpt_retain)
            except ckpt.SimulatedKill:
                print(f"chaos: simulated kill during save at step {t} — "
                      f"'latest' still points at the previous good step")
                mw.emit("checkpoint", t=t, killed=True)
            return
        with phase("checkpoint"):
            ckpt.save_step(args.ckpt, t, lambda p: save_into(p, t),
                           retain=args.ckpt_retain)
        print(f"checkpointed -> {ckpt.step_dir(args.ckpt, t)}")
        mw.emit("checkpoint", t=t, killed=False,
                path=str(ckpt.step_dir(args.ckpt, t)))

    def load_from(path):
        """Restore into the freshly-initialized state — resharding the
        worker axis when the save's W differs from this run's.  Client
        mode restores the STORE instead (same client count required; the
        cohort size —--workers— may change freely, that's just a
        different participation rate)."""
        recorded = ckpt.load_meta(path).get("meta", {})
        if store is not None:
            if "clients" not in recorded:
                raise ValueError(
                    "checkpoint was saved without --clients (a plain "
                    "worker state, not a client store) — resume it "
                    "without --clients")
            if int(recorded["clients"]) != args.clients:
                raise ValueError(
                    f"checkpoint holds {recorded['clients']} clients but "
                    f"--clients is {args.clients}; the client population "
                    f"is fixed for a run (change --workers to change the "
                    f"participation rate instead)")
            ckpt.validate_flat_meta(
                recorded, bundle.engine.spec,
                compressors=comm_mod.pair_meta(bundle.engine.compressors),
                moments=ckpt.moments_meta(vrl))
            store.load_tree(ckpt.restore(path, store.to_tree()))
            return state        # the next round's gather installs the rows
        if "clients" in recorded:
            raise ValueError(
                f"checkpoint is a client store ({recorded['clients']} "
                f"clients) — pass --clients {recorded['clients']} to "
                f"resume it")
        if bundle.engine is None:
            return ckpt.restore(path, state)
        comps_meta = comm_mod.pair_meta(bundle.engine.compressors)
        mom = ckpt.moments_meta(vrl)
        if bundle.engine.grid is None:
            w_saved = ckpt.saved_workers(path)
            if w_saved != args.workers:
                print(f"resume: resharding {w_saved} -> {args.workers} "
                      f"workers (Δ recentred, EF residuals dropped)")
                return ckpt.restore_resharded(
                    path, state, bundle.engine.spec,
                    compressors=comps_meta, moments=mom)
        return ckpt.restore_flat_state(
            path, state, bundle.engine.spec, grid=bundle.engine.grid,
            compressors=comps_meta, moments=mom)

    start_t = 0
    if args.resume:
        if args.resume == "auto":
            if not args.ckpt:
                raise SystemExit("--resume auto finds checkpoints under "
                                 "--ckpt; pass --ckpt too")
            found = ckpt.latest_step(args.ckpt)
            if found is None:
                print("resume auto: no complete checkpoint — fresh start")
                resume_path = None
            else:
                start_t, resume_path = found
        else:
            resume_path = args.resume
        if args.resume != "auto" or resume_path is not None:
            try:
                restored = load_from(resume_path)
            except (ValueError, KeyError, FileNotFoundError) as e:
                raise SystemExit(f"--resume {args.resume}: {e}")
            state = jax.tree.map(jnp.asarray, restored)
            rec_meta = ckpt.load_meta(resume_path).get("meta", {})
            start_t = int(rec_meta.get("step", start_t))
            # data continuity: reuse the SAVED shard assignment instead of
            # re-drawing the stream; a changed unit count re-splits it
            # exactly once (data.partition.repartition) and the re-split
            # is what later checkpoints record
            saved_assign = rec_meta.get("assignment")
            if saved_assign is not None:
                saved_assign = partition_mod.assignment_from_meta(
                    saved_assign)
                if len(saved_assign) != units:
                    print(f"resume: re-splitting saved data assignment "
                          f"{len(saved_assign)} -> {units} units (shard "
                          f"skews preserved)")
                    assignment = partition_mod.repartition(saved_assign,
                                                           units)
                else:
                    assignment = saved_assign
            print(f"resumed step {start_t} from {resume_path}")
            mw.emit("restore", t=start_t, path=str(resume_path),
                    workers=args.workers)
    data = assigned_token_stream(assignment, args.seq, cfg.vocab_size,
                                 steps=args.steps, batch=args.batch,
                                 alpha=args.alpha,
                                 identical=args.identical, seed=args.seed)

    def emit_final(state, steps_done, *, note="", **extra):
        """The ONE end-of-run emit path — the normal end and the
        checkpoint-step >= --steps early exit both land here, so both
        get the real averaged-model eval (the early exit used to write
        --loss-out with avg_model_loss: null)."""
        if not (args.loss_out or mw.active):
            return
        with phase("eval"):
            toks_f = jnp.asarray(data[args.steps - 1])
            labels_f = jnp.roll(toks_f, -1, axis=-1)
            el = float(eval_avg(state, toks_f, labels_f))
        out = {"steps": int(steps_done), "final_loss": el,
               "avg_model_loss": el}
        if args.loss_out:
            with open(args.loss_out, "w") as f:
                json.dump(out, f)
            print(f"loss-out: avg_model_loss {el:.4f}{note} -> "
                  f"{args.loss_out}")
        mw.emit("run_end", **out, **extra,
                phases=timers.summary() if timers is not None else None)
        mw.close()

    if start_t >= args.steps:
        print(f"resume: checkpoint step {start_t} >= --steps "
              f"{args.steps} — nothing to do")
        if store is not None:
            # the restored rows live in the client store, not in the
            # fresh-init device state — gather the step's cohort so the
            # averaged-model eval sees restored clients, exactly as the
            # normal end path would
            cohort = clients_mod.sample_cohort(args.clients, args.workers,
                                               start_t, args.seed)
            state = store.gather(
                cohort, member=getattr(state, "member", ()), like=state,
                seed_params=(args.clients > args.workers
                             and not bundle.engine.algo.has_center))
        emit_final(state, start_t, note=" (restored checkpoint)")
        return 0

    t0 = time.time()
    if args.round:
        # Round-based execution: ONE dispatch per communication period (k
        # scanned local steps + sync, state donated, losses buffered
        # device-side), tokens prefetched per round.  VRL-SGD-W's warmup
        # runs the first period as a 1-step round (compiled separately,
        # once).  A CommSchedule sizes each round from its stage; the
        # RoundCache keys one compiled executable per distinct k, so a
        # stagewise run compiles at most len(stages) rounds.  --log-every
        # counts rounds here.
        k_round = hier.k1 if hier else args.k
        warm_first = (sched is None and args.warmup
                      and engine_mod.get_spec(args.algorithm).warmup_aware)
        round_fn = engine_mod.RoundCache(bundle.round_step)
        # chaos machinery: the fault round is its own RoundCache (the
        # (k, W) multiplier is one more scanned operand, so it compiles
        # separately and the clean path stays the clean executable)
        fault_round_fn = (engine_mod.RoundCache(bundle.round_step_fault)
                          if faults is not None else None)
        set_member = None
        cur_mask = np.ones(args.workers, np.float32)
        if membership and bundle.engine is not None:
            set_member = jax.jit(bundle.engine.set_membership)
            if hasattr(state, "member") and not isinstance(
                    state.member, tuple):
                cur_mask = np.asarray(state.member.active).reshape(-1)
        health_fn = jax.jit(bundle.health) if args.guard else None
        # client sampling: the cohort recentre is its own tiny jit (the
        # compiled round stays the UNCHANGED clean executable), and it only
        # runs when the cohort is a strict subset — full participation
        # must stay bitwise the storeless path
        recenter_fn = None
        if store is not None and args.clients > args.workers:
            recenter_fn = jax.jit(bundle.engine.recenter_drift,
                                  donate_argnums=(0,))
        # strict-subset cohorts start the round FROM the server consensus
        # (the federated broadcast): what persists per client is the
        # control variate / bias / moments / residual.  A client
        # re-entering with params from many rounds ago would otherwise
        # book the whole consensus gap into its Δ via (x̂' − x_i)/(k·γ)
        # and blow up its next participation.  EASGD keeps per-client
        # params — persistent local params are elastic averaging's point.
        seed_cohort = (store is not None and args.clients > args.workers
                       and not bundle.engine.algo.has_center)
        last_good = None        # last healthy round-mean loss (--guard)
        retries = 0
        t, r = start_t, 0
        while t < args.steps:
            if sched is not None:
                rk = sched.period_starting_at(t)
            else:
                rk = 1 if (warm_first and t == 0) else k_round
            if args.steps - t < rk:
                # tail shorter than a round: finish per-step so the sync
                # cadence matches the per-step driver exactly (no
                # off-cadence closing sync, no extra whole-round compile).
                # Under overlap the per-step sync would not maintain the
                # pend buffer, so the tail runs local steps only — its
                # contribution folds at the next boundary, which never
                # comes (the tail is the end of the run).
                cohort = None
                if store is not None:
                    cohort = clients_mod.sample_cohort(
                        args.clients, args.workers, t, args.seed)
                    with phase("gather"):
                        state = store.gather(cohort,
                                             member=getattr(state, "member",
                                                            ()),
                                             like=state,
                                             seed_params=seed_cohort)
                    mw.emit("cohort", t=t, clients=cohort.tolist())
                step = jax.jit(bundle.local_step if args.overlap
                               else bundle.train_step)
                while t < args.steps:
                    toks = jnp.asarray(data[t] if cohort is None
                                       else data[t][cohort])
                    labels = jnp.roll(toks, -1, axis=-1)
                    state, loss = step(state, toks, labels)
                    t += 1
                    if args.ckpt and t % args.ckpt_every == 0:
                        if store is not None:
                            store.scatter(state, cohort)
                        checkpoint(t)
                if store is not None:
                    store.scatter(state, cohort)
                el = eval_avg(state, toks, labels)
                print(f"step {t:5d} (tail)  "
                      f"local_loss {float(loss):.4f}  "
                      f"avg_model_loss {float(el):.4f}  "
                      f"({(time.time()-t0)/t:.2f}s/step)")
                mw.emit("tail", t=t, local_loss=float(loss),
                        avg_model_loss=float(el))
                break
            # client sampling: draw the round's cohort and load its rows
            # into the device buffers — one contiguous copy per flat
            # buffer.  The draw depends only on (seed, round-start step),
            # so a resumed or rolled-back run re-gathers the same cohort.
            cohort = None
            if store is not None:
                cohort = clients_mod.sample_cohort(
                    args.clients, args.workers, t, args.seed)
                with phase("gather"):
                    state = store.gather(cohort,
                                         member=getattr(state, "member", ()),
                                         like=state,
                                         seed_params=seed_cohort)
                mw.emit("cohort", t=t, clients=cohort.tolist())
            # membership repair at the round boundary: fold the fault
            # schedule's crash/rejoin history into a mask; one jitted
            # set_membership call redistributes the leavers' Δ over the
            # survivors (Σ Δ stays 0) and re-anchors rejoiners
            if faults is not None and set_member is not None:
                mask = faults.active_at(t, args.workers)
                if not np.array_equal(mask, cur_mask):
                    with phase("membership"):
                        state = set_member(state, mask)
                    cur_mask = mask
                    print(f"membership: step {t} active "
                          f"{int(mask.sum())}/{args.workers} "
                          f"{mask.astype(int).tolist()}")
                    mw.emit("membership", t=t,
                            active=mask.astype(int).tolist(),
                            n_active=int(mask.sum()))
            # a strict-subset cohort's corrections sum to the cohort mean,
            # not zero — recentre so the round's sync math holds
            if recenter_fn is not None:
                state = recenter_fn(state)
            snap = jax.device_get(state) if args.guard else None
            with phase("data"):
                toks = jnp.asarray(data[t:t + rk] if cohort is None
                                   else data[t:t + rk][:, cohort])
                labels = jnp.roll(toks, -1, axis=-1)
            gmul = (faults.grad_mul(t, rk, args.workers)
                    if faults is not None else None)
            if gmul is not None:
                print(f"chaos: gradient fault in round [{t}, {t + rk})")
                mw.emit("fault", t=t, k=rk,
                        events=faults.events_in(t, t + rk))
            if args.profile_round and r + 1 == args.profile_round \
                    and not profiled:
                jax.profiler.start_trace(args.profile_dir)
                profiling = True
            t_round = time.perf_counter()
            with jax.profiler.StepTraceAnnotation("round", step_num=r), \
                    phase("round"):
                if gmul is not None:
                    state, losses = fault_round_fn(state, toks, labels,
                                                   jnp.asarray(gmul))
                else:
                    state, losses = round_fn(state, toks, labels)
                if timers is not None or profiling:
                    # timed rounds block here so the sample is the real
                    # round wall-clock, not the dispatch latency
                    losses = jax.block_until_ready(losses)
            round_s = time.perf_counter() - t_round
            if profiling:
                jax.profiler.stop_trace()
                profiling, profiled = False, True
                print(f"profiler: traced round {r + 1} -> "
                      f"{args.profile_dir}")
            loss_r = (float(jnp.mean(losses))
                      if (health_fn is not None or mw.active) else None)
            diverged = None
            if health_fn is not None:
                if not bool(health_fn(state, jnp.asarray(loss_r))):
                    diverged = "non-finite state"
                elif (last_good is not None
                      and loss_r > _BLOWUP_FACTOR * last_good
                      + _BLOWUP_SLACK):
                    # a finite blow-up (e.g. a scale@w:s:mult poison)
                    # passes every finiteness check — catch it on the
                    # loss trend instead
                    diverged = (f"loss blow-up ({loss_r:.3g} vs last "
                                f"good {last_good:.3g})")
            # algorithm-health diagnostics at --log-every cadence (plus
            # the first/last round and any diverged round): one read-only
            # jit over the post-round state, separate from the round
            drec = None
            if diag_fn is not None and ((r + 1) % args.log_every == 0
                                        or r == 0
                                        or t + rk >= args.steps
                                        or diverged is not None):
                with phase("diag"):
                    drec = obs_diag.to_record(diag_fn(state))
                alarms = obs_diag.check_alarms(
                    drec, invariant_threshold=args.invariant_alarm)
                drec["alarms"] = alarms
                if alarms and health_fn is not None and diverged is None:
                    # the invariant monitor feeds the SAME rollback path
                    # as the loss/finiteness guard
                    diverged = "invariant alarm: " + "; ".join(alarms)
                elif alarms and health_fn is None:
                    print("invariant alarm (no --guard, continuing): "
                          + "; ".join(alarms))
            if diverged is not None:
                t_fail = t + rk
                if retries >= args.max_retries:
                    mw.emit("rollback", t_fail=t_fail, reason=diverged,
                            retry=retries, aborted=True)
                    mw.close()
                    raise SystemExit(
                        f"divergence guard: state still diverged after "
                        f"{retries} rollbacks at step {t + rk} — aborting")
                retries += 1
                time.sleep(min(0.05 * 2 ** retries, 1.0))   # backoff
                found = ckpt.latest_step(args.ckpt) if args.ckpt else None
                if found is not None and found[0] <= t:
                    back_t, back_path = found
                    state = jax.tree.map(jnp.asarray, load_from(back_path))
                    t = back_t
                else:                       # no checkpoint: round-start
                    state = jax.tree.map(jnp.asarray, snap)
                if set_member is not None and hasattr(state, "member") \
                        and not isinstance(state.member, tuple):
                    cur_mask = np.asarray(state.member.active).reshape(-1)
                print(f"divergence guard: {diverged} — rolled back "
                      f"to step {t} (retry {retries}/{args.max_retries})")
                mw.emit("rollback", t_fail=t_fail, reason=diverged,
                        back_to=t, retry=retries)
                if drec is not None:
                    mw.emit("diag", t=t_fail, r=r + 1, rolled_back=True,
                            **drec)
                continue
            if health_fn is not None:
                last_good = loss_r
            retries = 0
            # only a HEALTHY round's rows reach the store: a rolled-back
            # round never scatters, so its clients keep pre-round state
            if store is not None:
                with phase("scatter"):
                    store.scatter(state, cohort)
            t += rk
            r += 1
            mw.emit("round", t=t, r=r, k=rk, loss=loss_r,
                    seconds=round_s,
                    wire_bytes=None if wire is None
                    else wire["wire_bytes"])
            mw.emit("sync", t=t, r=r, k_eff=rk,
                    participants=int(cur_mask.sum()),
                    wire_bytes=None if wire is None
                    else wire["wire_bytes"],
                    wire_bytes2=None if wire is None
                    else wire["wire_bytes2"])
            if drec is not None:
                mw.emit("diag", t=t, r=r, **drec)
                if args.diag:
                    print(f"diag: step {t:5d} (round {r})  "
                          + obs_diag.describe(drec))
            if r % args.log_every == 0 or r == 1 or t >= args.steps:
                with phase("eval"):
                    el = float(eval_avg(state, toks[-1], labels[-1]))
                ll = (loss_r if loss_r is not None
                      else float(jnp.mean(losses)))
                print(f"step {t:5d} (round {r})  "
                      f"local_loss {ll:.4f}  "
                      f"avg_model_loss {float(el):.4f}  "
                      f"({(time.time()-t0)/t:.2f}s/step)")
                mw.emit("eval", t=t, r=r, local_loss=ll,
                        avg_model_loss=float(el))
            if args.ckpt and t // args.ckpt_every > (t - rk) // args.ckpt_every:
                checkpoint(t)
    else:
        step = jax.jit(bundle.train_step)
        for t in range(start_t, args.steps):
            toks = jnp.asarray(data[t])
            labels = jnp.roll(toks, -1, axis=-1)
            state, loss = step(state, toks, labels)
            if (t + 1) % args.log_every == 0 or t == 0:
                el = eval_avg(state, toks, labels)
                print(f"step {t+1:5d}  local_loss {float(loss):.4f}  "
                      f"avg_model_loss {float(el):.4f}  "
                      f"({(time.time()-t0)/(t+1):.2f}s/step)")
                mw.emit("eval", t=t + 1, local_loss=float(loss),
                        avg_model_loss=float(el))
                if diag_fn is not None:
                    drec = obs_diag.to_record(diag_fn(state))
                    drec["alarms"] = obs_diag.check_alarms(
                        drec, invariant_threshold=args.invariant_alarm)
                    mw.emit("diag", t=t + 1, **drec)
                    if args.diag:
                        print(f"diag: step {t+1:5d}  "
                              + obs_diag.describe(drec))
            if args.ckpt and (t + 1) % args.ckpt_every == 0:
                checkpoint(t + 1)
    extra = ""
    end_meta = {"wall_s_train": round(time.time() - t0, 3)}
    if args.round:
        extra = (f", {round_fn.compiles} round executable"
                 f"{'s' if round_fn.compiles != 1 else ''} "
                 f"(k={list(round_fn.cached_ks)})")
        end_meta.update(rounds=r, round_executables=round_fn.compiles)
        # the compiled round's attention core, read from its HLO text
        # after the last round (obs.scopemap)
        attn = scopemap.attention_executor()
        if attn is not None:
            end_meta["attention"] = attn
            extra += (f", attention {attn['executor']} "
                      f"({attn['kernels']} kernels)")
    print(f"done: {args.steps} steps in {time.time()-t0:.1f}s{extra}")
    # final metrics off the average model over one fresh batch — the
    # chaos CI gate compares --loss-out across faulted/clean runs
    emit_final(state, args.steps, **end_meta)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
