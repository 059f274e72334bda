import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the platform and the device count
#   on first init.  The dry-run compiles on the host and never takes an
#   accelerator; 512 placeholder host devices back both the 16x16
#   single-pod mesh (first 256 devices) and the 2x16x16 multi-pod mesh.

"""Multi-pod dry-run driver.

For every (architecture x input-shape x mesh) combination this lowers the
real step function (train_step / prefill / serve_step) with ShapeDtypeStruct
inputs (zero allocation), compiles it for the production mesh, and records:

  * memory_analysis()   — per-device bytes (does it fit HBM?)
  * cost_analysis()     — per-device FLOPs / bytes for the roofline
  * collective bytes    — parsed from optimized HLO (loop-aware)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun.jsonl
"""
import argparse
import dataclasses
import functools
import json
import math
import sys
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import compressors as comm_mod
from repro.configs.base import (EngineConfig, HierConfig, InputShape,
                                MeshConfig, VRLConfig)
from repro.configs import registry
from repro.core import engine as engine_mod
from repro.core import schedule as schedule_mod
from repro.launch import roofline as rl
from repro.launch.mesh import (CHIPS_PER_POD, HBM_PER_CHIP, make_mesh,
                               make_production_mesh)
from repro.models import transformer
from repro.models.param import abstract as abstract_params
from repro.serve.engine import make_prefill, make_serve_step
from repro.sharding import specs as sh
from repro.train.train_loop import make_train_step


# --------------------------------------------------------------------- mesh
def build_mesh(mesh_cfg: MeshConfig):
    n = math.prod(mesh_cfg.shape)
    return make_mesh(mesh_cfg.shape, mesh_cfg.axis_names,
                     devices=jax.devices()[:n])


def _data_axes(mesh_cfg: MeshConfig):
    return tuple(mesh_cfg.worker_axes) + tuple(mesh_cfg.fsdp_axes)


def _axis_size(mesh_cfg: MeshConfig, axes) -> int:
    sizes = dict(zip(mesh_cfg.axis_names, mesh_cfg.shape))
    return math.prod(sizes[a] for a in axes) if axes else 1


# -------------------------------------------------------------- input specs
def input_specs(arch_id: str, shape_id: str, mesh_cfg: MeshConfig,
                cfg=None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this combo."""
    cfg = cfg or registry.padded_arch(arch_id, mesh_cfg)
    shape = registry.get_shape(shape_id)
    w = mesh_cfg.num_workers
    if shape.kind == "train":
        b = shape.global_batch // w
        if cfg.frontend == "codec":
            inp = jax.ShapeDtypeStruct((w, b, shape.seq_len, cfg.frontend_dim),
                                       jnp.bfloat16)
        else:
            inp = jax.ShapeDtypeStruct((w, b, shape.seq_len), jnp.int32)
        lab = jax.ShapeDtypeStruct((w, b, shape.seq_len), jnp.int32)
        return {"tokens": inp, "labels": lab}
    if shape.kind == "prefill":
        if cfg.frontend == "codec":
            inp = jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len, cfg.frontend_dim),
                jnp.bfloat16)
        else:
            inp = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                       jnp.int32)
        return {"tokens": inp}
    # decode: one new token against a seq_len cache
    window = _decode_window(cfg, shape)
    cache = jax.eval_shape(
        lambda: transformer.init_cache(cfg, shape.global_batch, shape.seq_len,
                                       dtype=jnp.bfloat16, window=window))
    if cfg.frontend == "codec":
        tok = jax.ShapeDtypeStruct((shape.global_batch, 1, cfg.frontend_dim),
                                   jnp.bfloat16)
    else:
        tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return {"tokens": tok, "cache": cache,
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}


def _decode_window(cfg, shape: InputShape) -> Optional[int]:
    """long_500k needs sub-quadratic attention: SSM/hybrid run natively,
    full-attention archs run the sliding-window variant."""
    if shape.name == "long_500k" and cfg.family not in ("ssm",):
        if cfg.attn_window is not None:
            return cfg.attn_window
        return cfg.long_context_window
    return cfg.attn_window


# ---------------------------------------------------------------- shardings
def _maybe(axes, size_needed: int, mesh_cfg: MeshConfig):
    """Axes tuple if it divides size_needed, else None (replicated)."""
    if not axes:
        return None
    if size_needed % _axis_size(mesh_cfg, axes) == 0:
        return axes if len(axes) > 1 else axes[0]
    return None


def batch_sharding_spec(mesh_cfg: MeshConfig, batch: int, extra: int,
                        *, worker_stacked: bool) -> P:
    if worker_stacked:
        lead = sh._norm(tuple(mesh_cfg.worker_axes))
        inner = _maybe(tuple(mesh_cfg.fsdp_axes), batch, mesh_cfg)
        return P(lead, inner, *([None] * extra))
    axes = _maybe(_data_axes(mesh_cfg), batch, mesh_cfg)
    return P(axes, *([None] * extra))


def cache_specs(cfg, mesh_cfg: MeshConfig, batch: int, seq_len: int = 0):
    """PartitionSpec tree mirroring init_cache's (layer-stacked) structure.

    KV layout policy: shard kv heads over the tensor axis when divisible;
    otherwise shard the cache SEQ dim (distributed flash-decode: per-shard
    partial softmax combined by small all-reduces) — replicating a 32k cache
    across 16 tensor shards would blow HBM on the GQA-8 architectures.
    """
    t = mesh_cfg.tensor_size
    bax = _maybe(_data_axes(mesh_cfg), batch, mesh_cfg)
    tax = sh._norm(tuple(mesh_cfg.tensor_axes))
    kvh = None
    seq_ax = None
    if cfg.num_kv_heads and cfg.num_kv_heads % t == 0:
        kvh = tax
    elif seq_len and seq_len % t == 0:
        seq_ax = tax
    ssmh = tax if cfg.ssm_state and cfg.ssm_num_heads % t == 0 else None

    attn = {"k": P(None, bax, seq_ax, kvh, None),
            "v": P(None, bax, seq_ax, kvh, None)}
    ssm_c = {"state": P(None, bax, ssmh, None, None),
             "conv": P(None, bax, None, None)}
    if cfg.family == "ssm":
        return ssm_c
    if cfg.family == "hybrid":
        return {"attn": attn, "ssm": ssm_c}
    return attn


def state_specs(cfg, mesh_cfg: MeshConfig, vrl_cfg: VRLConfig):
    """PartitionSpec tree for WorkerState."""
    from repro.core.types import CommState, WorkerState
    defs = transformer.model_defs(cfg)
    pspec = sh.partition_specs(defs, cfg, mesh_cfg)
    wspec = jax.tree.map(lambda s: sh.worker_stacked_spec(s, mesh_cfg),
                         pspec, is_leaf=lambda x: isinstance(x, P))
    if vrl_cfg.inner_optimizer == "sgd" and not vrl_cfg.momentum:
        inner = ()
    elif vrl_cfg.inner_optimizer == "adam":
        from repro.optim.optimizers import AdamState
        inner = AdamState(wspec, wspec, P())
    else:
        inner = wspec
    center = pspec if vrl_cfg.algorithm == "easgd" else None
    spec = engine_mod.get_spec(vrl_cfg.algorithm)
    bias = wspec if engine_mod.use_bias(spec, vrl_cfg) else None
    comp, _ = comm_mod.resolve_pair(vrl_cfg)
    comm = ()
    if comp is not None:
        comm = CommState(
            resid=(wspec if comp.error_feedback else ()),
            ref=(() if (spec.grad_all_reduce or spec.sync == "none")
                 else pspec))
    return WorkerState(params=wspec, delta=wspec, inner=inner, center=center,
                       step=P(), last_sync=P(), bias=bias, comm=comm)


# ------------------------------------------------------------------- lower
@dataclasses.dataclass
class DryrunResult:
    arch: str
    shape: str
    mesh: str
    fn: str
    ok: bool
    compile_s: float
    per_device_bytes: int
    roofline: Optional[rl.Roofline]
    error: str = ""
    compressor: str = ""         # active compressor for this fn's level
    comp_bytes: int = 0          # compressed wire bytes of the sync payload

    def to_json(self) -> dict:
        d = {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "fn": self.fn, "ok": self.ok, "compile_s": round(self.compile_s, 2),
            "per_device_bytes": self.per_device_bytes, "error": self.error,
        }
        if self.compressor:
            d.update(compressor=self.compressor, comp_bytes=self.comp_bytes)
        if self.roofline:
            r = self.roofline
            d.update(hlo_flops=r.hlo_flops, hlo_bytes=r.hlo_bytes,
                     coll_bytes=r.coll_bytes, dci_bytes=r.dci_bytes,
                     model_flops=r.model_flops,
                     t_compute=r.t_compute, t_memory=r.t_memory,
                     t_collective=r.t_collective, bottleneck=r.bottleneck,
                     useful_ratio=r.useful_ratio, coll_detail=r.coll_detail)
        return d


def _mem_bytes(compiled) -> int:
    try:
        ma = compiled.memory_analysis()
        return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                   + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
    except Exception:
        return -1


def _model_flops_train(cfg, shape: InputShape) -> float:
    return 6.0 * cfg.active_param_count() * shape.global_batch * shape.seq_len


def _model_flops_prefill(cfg, shape: InputShape) -> float:
    return 2.0 * cfg.active_param_count() * shape.global_batch * shape.seq_len


def _model_flops_decode(cfg, shape: InputShape) -> float:
    return 2.0 * cfg.active_param_count() * shape.global_batch


# --------------------------------------------------- engine-state memory
def _leaf_per_device(shape, nbytes: int, workers: int, shards: int) -> int:
    """Per-device bytes of one engine-state leaf under the engine's
    placement rules: worker-stacked leading dims ((W, ...) or pod-major
    (P, D, ...)) split over the worker axes, and the row dim (-2) splits
    over the shard axis exactly when ``core.engine._row_axis`` would
    shard it — ``shape[-2] > 1 and shape[-2] % shards == 0``.  Everything
    else (step counters, pend_k) replicates."""
    div = 1
    if len(shape) >= 3 and shape[0] == workers:
        div *= workers                              # (W, R, C) stacks
    elif len(shape) >= 4 and shape[0] * shape[1] == workers:
        div *= workers                              # (P, D, R, C) grids
    if (shards > 1 and len(shape) >= 2
            and shape[-2] > 1 and shape[-2] % shards == 0):
        div *= shards
    return nbytes // div


def _engine_state_bytes(cfg, vrl_cfg: VRLConfig, workers: int) -> dict:
    """{leaf path: (shape, dtype, bytes, per_device_bytes)} for the flat
    engine's state, from ``eval_shape`` alone — no allocation, no compile,
    so it works at kimi-k2-1t scale on any host."""
    template = jax.eval_shape(functools.partial(
        transformer.init_params, cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    eng = engine_mod.make_engine(vrl_cfg, template)
    state = jax.eval_shape(lambda: eng.init(
        transformer.init_params(cfg, jax.random.PRNGKey(0),
                                dtype=jnp.bfloat16), workers))
    shards = vrl_cfg.engine.shards
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        key = "/".join(str(getattr(p, "name", getattr(p, "key",
                       getattr(p, "idx", p)))) for p in path)
        nb = int(np.prod(leaf.shape, dtype=np.int64)
                 * jnp.dtype(leaf.dtype).itemsize) if leaf.shape else \
            jnp.dtype(leaf.dtype).itemsize
        out[key] = {"shape": list(leaf.shape), "dtype": str(leaf.dtype),
                    "bytes": nb,
                    "per_device_bytes": _leaf_per_device(
                        leaf.shape, nb, workers, shards)}
    return out


def engine_mem(arch_id: str, *, algorithm: str = "vrl_sgd",
               inner: str = "adam", workers: int = 0, shards: int = 1,
               moment_dtype: str = "float32", sm3: bool = False,
               clients: int = 0, verbose: bool = True) -> dict:
    """Analytic engine-state HBM artifact for one (arch, sharding,
    moment-storage) point, plus the unsharded-fp32 baseline.

    Fields:
      buffers            — per engine-state leaf: shape, dtype, total
                           bytes, per-device bytes under the placement
                           rules (worker dims / worker axes, row dim /
                           shard axis)
      total_bytes        — engine state summed over all workers (what a
                           checkpoint holds; placement-invariant)
      per_device_bytes   — what ONE chip persists between steps
      baseline_per_device_bytes, reduction
                         — the same arch at shards=1 / fp32 / no SM3,
                           and baseline/current (the headline factor)
      devices_used       — workers x shards chips the placement occupies
      fits_pod           — devices_used <= CHIPS_PER_POD and
                           per_device_bytes <= HBM_PER_CHIP (v5e 16 GiB)
      t_engine_pass      — roofline HBM seconds of one fused local step's
                           engine traffic (2x per-device bytes / HBM BW)
      client_store_bytes — with ``clients`` = M > 0: the HOST bytes of a
                           ``core.clients.ClientStore`` holding M logical
                           clients behind the W-slot device window — each
                           per-participant leaf ((W, ...) leading axis)
                           scaled by M/W, globals counted once.  Host
                           RAM, not HBM: it never rides a chip.
    """
    mesh_cfg = registry.mesh_roles(arch_id, multi_pod=False, serving=False)
    cfg = registry.padded_arch(arch_id, mesh_cfg)
    workers = workers or mesh_cfg.num_workers
    delta_dt = ("bfloat16" if (arch_id in registry._FSDP_ARCHS
                               or os.environ.get("VRL_DELTA_BF16"))
                else "float32")

    def _cfg(s, mdt, sm):
        return VRLConfig(algorithm=algorithm, inner_optimizer=inner,
                         update_backend="xla", delta_dtype=delta_dt,
                         moment_dtype=mdt, sm3=sm,
                         engine=EngineConfig(shards=s))

    bufs = _engine_state_bytes(cfg, _cfg(shards, moment_dtype, sm3), workers)
    base = _engine_state_bytes(cfg, _cfg(1, "float32", False), workers)
    per_dev = sum(b["per_device_bytes"] for b in bufs.values())
    base_dev = sum(b["per_device_bytes"] for b in base.values())
    devices = workers * shards
    art = {
        "arch": arch_id, "algorithm": algorithm, "inner": inner,
        "workers": workers, "shards": shards,
        "moment_dtype": moment_dtype, "sm3": sm3,
        "delta_dtype": delta_dt,
        "buffers": bufs,
        "total_bytes": sum(b["bytes"] for b in bufs.values()),
        "per_device_bytes": per_dev,
        "baseline_per_device_bytes": base_dev,
        "reduction": round(base_dev / per_dev, 2) if per_dev else 0.0,
        "devices_used": devices,
        "hbm_per_chip": HBM_PER_CHIP, "chips_per_pod": CHIPS_PER_POD,
        "fits_pod": (devices <= CHIPS_PER_POD
                     and per_dev <= HBM_PER_CHIP),
        "t_engine_pass": rl.engine_pass_time(per_dev),
    }
    if clients:
        if clients < workers:
            raise ValueError(f"clients ({clients}) must be >= workers "
                             f"({workers}) — the cohort size is the "
                             f"worker count")
        store = 0
        for b in bufs.values():
            per_participant = (len(b["shape"]) >= 3
                               and b["shape"][0] == workers)
            store += (b["bytes"] // workers * clients if per_participant
                      else b["bytes"])
        art["clients"] = clients
        art["client_store_bytes"] = store
    if verbose:
        extra = (f", client store {art['client_store_bytes']/2**30:.2f} "
                 f"GiB host (M={clients})" if clients else "")
        print(f"[engine-mem] {arch_id} {algorithm}/{inner} W={workers} "
              f"shards={shards} moments={moment_dtype}"
              f"{'+sm3' if sm3 else ''}: "
              f"{per_dev/2**30:.2f} GiB/device "
              f"(baseline {base_dev/2**30:.2f}, {art['reduction']}x), "
              f"{devices} chips, fits_pod={art['fits_pod']}{extra}")
    return art


def lower_one(arch_id: str, shape_id: str, *, multi_pod: bool,
              vrl_cfg: Optional[VRLConfig] = None,
              fn_kind: Optional[str] = None, verbose: bool = True,
              unrolled: bool = False, algorithm: str = "vrl_sgd",
              comm_period: int = 20, k1: int = 5, k2: int = 20,
              comm_schedule: Optional[str] = None, round_k: int = 0,
              backend: str = "fused",
              overlap: bool = False, deadline: float = 0.0,
              compress: Optional[str] = None,
              compress2: Optional[str] = None,
              shards: int = 1, moment_dtype: str = "float32",
              sm3: bool = False,
              mesh_override: Optional[dict] = None,
              cfg_override: Optional[dict] = None, tag: str = "",
              last_only: bool = False, no_remat: bool = False):
    """Lower+compile one combination. fn_kind in
    {train, local, sync, sync1, sync2, round, prefill, decode} (default by
    shape kind; sync1/sync2 are the hierarchical per-level syncs and require
    ``algorithm="hier_vrl_sgd"``; "round" lowers ``bundle.round_step`` — one
    scanned communication period with the state donated, tokens stacked
    (k, W, b, s)).

    The train family lowers through ``backend`` ("fused" default: the
    flat-buffer engine, so the memory/cost/collective-bytes artifacts
    reflect the production TPU update path even when lowering on a CPU
    host; "auto"/"xla" lower the plain-jnp executor, "reference" the
    per-leaf tree path — one flat all-reduce per sync and one per-axis
    all-reduce per hierarchical sync level in every engine variant).

    ``unrolled=True`` unrolls the layer scan so cost_analysis() counts every
    layer (XLA's HLO cost analysis counts a while-loop body ONCE); use the
    scanned variant for the memory/fit artifact and the unrolled one for
    roofline terms.

    Link-tier attribution (``Roofline.dci_bytes``) and the per-level
    compressed wire bytes are exact on the PER-LEVEL lowerings: "sync2"
    prices its cross-pod all-reduce at DCI bandwidth and reports the
    level-2 compressor; everything else is ICI / level-1.  Hierarchical
    "round"/"train" lowerings aggregate BOTH levels in one HLO, so their
    collective term is priced at ICI rate and comp_bytes shows level 1
    only — use the sync1/sync2 artifacts as the tier-attributed source of
    truth."""
    serving = fn_kind in ("prefill", "decode") or (
        fn_kind is None and registry.get_shape(shape_id).kind != "train")
    mesh_cfg = registry.mesh_roles(arch_id, multi_pod=multi_pod,
                                   serving=serving)
    if mesh_override:
        mesh_cfg = dataclasses.replace(mesh_cfg, **mesh_override)
    cfg = registry.padded_arch(arch_id, mesh_cfg)
    if cfg_override:
        cfg = dataclasses.replace(cfg, **cfg_override)
    shape = registry.get_shape(shape_id)
    hier = None
    if algorithm == "hier_vrl_sgd" and vrl_cfg is None:
        sizes = dict(zip(mesh_cfg.axis_names, mesh_cfg.shape))
        pods = sizes.get("pod", 1)
        hier = HierConfig(k1=k1, k2=k2,
                          grid=(pods, mesh_cfg.num_workers // pods))
    sched = (schedule_mod.parse_schedule(comm_schedule, comm_period)
             if comm_schedule else None)
    if compress2 and algorithm != "hier_vrl_sgd":
        # match launch/train.py: flat algorithms have one level
        raise ValueError("--compress2 drives the hierarchical cross-pod "
                         "sync2; flat algorithms have one level "
                         "(--compress)")
    vrl_cfg = vrl_cfg or VRLConfig(
        algorithm=algorithm, comm_period=comm_period, hier=hier,
        comm_schedule=sched, update_backend=backend,
        overlap=overlap, deadline=deadline,
        compress=(comm_mod.parse_compressor(compress) if compress
                  else None),
        compress2=(comm_mod.parse_compressor(compress2) if compress2
                   else None),
        moment_dtype=moment_dtype, sm3=sm3,
        # the production mesh carries no dedicated shard axis — engine row
        # shards REUSE the tensor axis "model" (launch/mesh.py), so
        # shards must equal that axis's size when > 1
        engine=EngineConfig(shards=shards,
                            shard_axis="model" if shards > 1 else "shard"),
        delta_dtype="bfloat16" if (arch_id in registry._FSDP_ARCHS
                                   or os.environ.get("VRL_DELTA_BF16"))
        else "float32")
    mesh = build_mesh(mesh_cfg)
    mesh_name = "multi" if multi_pod else "single"
    chips = math.prod(mesh_cfg.shape)
    if fn_kind is None:
        fn_kind = {"train": "train", "prefill": "prefill",
                   "decode": "decode"}[shape.kind]

    unroll = cfg.num_layers if unrolled else 1
    ins = input_specs(arch_id, shape_id, mesh_cfg, cfg=cfg)
    t0 = time.time()
    name = f"{arch_id}/{shape_id}/{mesh_name}/{fn_kind}"
    if unrolled:
        name += "/unrolled"
    if tag:
        name += f"/{tag}"

    eng_spec = None               # flat-buffer layout (for wire-bytes)
    with jax.set_mesh(mesh):
        if fn_kind in ("train", "local", "sync", "sync1", "sync2", "round"):
            fused = engine_mod.resolve_backend(vrl_cfg) != "reference"
            with warnings.catch_warnings():
                # dryrun's fused default deliberately lowers the Pallas
                # path on a CPU host (artifacts reflect the TPU plan;
                # nothing executes) — the engine's interpret-mode perf
                # warning does not apply to compile-only lowering
                warnings.filterwarnings(
                    "ignore", message=".*interpret-mode Pallas.*")
                bundle = make_train_step(cfg, vrl_cfg,
                                         remat=not no_remat, unroll=unroll,
                                         param_dtype=jnp.bfloat16,
                                         mesh=mesh if fused else None,
                                         worker_axes=mesh_cfg.worker_axes)
            state_abs = jax.eval_shape(
                lambda: bundle.init_state(jax.random.PRNGKey(0),
                                          mesh_cfg.num_workers))
            if bundle.engine is not None:
                eng_spec = bundle.engine.spec
            if fused:
                # hier axes resolve against THIS mesh: the single mesh has
                # no "pod" axis, so its (1, W) grid shards data only
                haxes = tuple(a if a in mesh_cfg.axis_names else None
                              for a in engine_mod.hier_config(vrl_cfg).axes)
                sh_ax = sh.engine_shard_axis(mesh_cfg, vrl_cfg.engine)
                st_spec = engine_mod.state_partition_specs(
                    state_abs, mesh_cfg.worker_axes, hier_axes=haxes,
                    shard_axis=sh_ax, shards=vrl_cfg.engine.shards)
            else:
                st_spec = state_specs(cfg, mesh_cfg, vrl_cfg)
            sts = sh.shardings(mesh, st_spec)
            extra = 2 if cfg.frontend == "codec" else 1
            tok_spec = batch_sharding_spec(
                mesh_cfg, shape.global_batch // mesh_cfg.num_workers,
                extra, worker_stacked=True)
            lab_spec = batch_sharding_spec(
                mesh_cfg, shape.global_batch // mesh_cfg.num_workers,
                1, worker_stacked=True)
            if fn_kind in ("sync", "sync1", "sync2"):
                step_fn = {"sync": bundle.sync_step,
                           "sync1": bundle.sync1_step,
                           "sync2": bundle.sync2_step}[fn_kind]
                if step_fn is None:
                    raise ValueError(
                        f"fn_kind {fn_kind!r} requires hier_vrl_sgd")
                fn = jax.jit(step_fn, in_shardings=(sts,),
                             out_shardings=sts)
                lowered = fn.lower(state_abs)
            elif fn_kind == "round":
                # one scanned communication period: (k, W, ...) stacks,
                # state donated — the artifacts show the no-copy round.
                # ``round_k`` overrides the length (a stagewise schedule's
                # per-stage round is the same executable at that stage's k)
                hcfg = engine_mod.hier_config(vrl_cfg)
                rk = round_k or (hcfg.k1 if algorithm == "hier_vrl_sgd"
                                 else vrl_cfg.comm_period)
                stk = jax.ShapeDtypeStruct(
                    (rk, *ins["tokens"].shape), ins["tokens"].dtype)
                slb = jax.ShapeDtypeStruct(
                    (rk, *ins["labels"].shape), ins["labels"].dtype)
                tks = sh.shardings(mesh, P(None, *tok_spec))
                lbs = sh.shardings(mesh, P(None, *lab_spec))
                fn = jax.jit(bundle.round_step, donate_argnums=(0,),
                             in_shardings=(sts, tks, lbs),
                             out_shardings=(sts,
                                            sh.shardings(mesh, P(None))))
                lowered = fn.lower(state_abs, stk, slb)
            else:
                step = (bundle.train_step if fn_kind == "train"
                        else bundle.local_step)
                fn = jax.jit(step,
                             in_shardings=(sts,
                                           sh.shardings(mesh, tok_spec),
                                           sh.shardings(mesh, lab_spec)),
                             out_shardings=(sts,
                                            sh.shardings(mesh, P())))
                lowered = fn.lower(state_abs, ins["tokens"], ins["labels"])
            mf = _model_flops_train(cfg, shape)
            if fn_kind in ("sync", "sync1", "sync2"):
                mf = 0.0
            elif fn_kind == "round":
                mf = mf * rk
        elif fn_kind == "prefill":
            pdefs = transformer.model_defs(cfg)
            params_abs = abstract_params(pdefs, jnp.bfloat16)
            pspec = sh.partition_specs(pdefs, cfg, mesh_cfg)
            prefill_fn = make_prefill(cfg, shape.seq_len, unroll=unroll,
                                      last_only=last_only)
            extra = 2 if cfg.frontend == "codec" else 1
            tok_spec = batch_sharding_spec(mesh_cfg, shape.global_batch,
                                           extra, worker_stacked=False)
            bax = _maybe(_data_axes(mesh_cfg), shape.global_batch, mesh_cfg)
            vax = _maybe(tuple(mesh_cfg.tensor_axes), cfg.vocab_size, mesh_cfg)
            logits_spec = P(bax, None, vax)
            eff = cfg.attn_window or shape.seq_len
            c_spec = cache_specs(cfg, mesh_cfg, shape.global_batch,
                                 seq_len=min(eff, shape.seq_len))
            fn = jax.jit(prefill_fn,
                         in_shardings=sh.shardings(
                             mesh, (pspec, tok_spec)),
                         out_shardings=sh.shardings(
                             mesh, (logits_spec, c_spec)))
            lowered = fn.lower(params_abs, ins["tokens"])
            mf = _model_flops_prefill(cfg, shape)
        elif fn_kind == "decode":
            pdefs = transformer.model_defs(cfg)
            params_abs = abstract_params(pdefs, jnp.bfloat16)
            pspec = sh.partition_specs(pdefs, cfg, mesh_cfg)
            window = _decode_window(cfg, shape)
            serve_fn = make_serve_step(cfg, window=window, unroll=unroll)
            eff = window if window is not None else shape.seq_len
            c_spec = cache_specs(cfg, mesh_cfg, shape.global_batch,
                                 seq_len=min(eff, shape.seq_len))
            extra = 2 if cfg.frontend == "codec" else 1
            tok_spec = batch_sharding_spec(mesh_cfg, shape.global_batch,
                                           extra, worker_stacked=False)
            bax = _maybe(_data_axes(mesh_cfg), shape.global_batch, mesh_cfg)
            vax = _maybe(tuple(mesh_cfg.tensor_axes), cfg.vocab_size, mesh_cfg)
            logits_spec = P(bax, None, vax)
            fn = jax.jit(serve_fn,
                         in_shardings=sh.shardings(
                             mesh, (pspec, c_spec, tok_spec, P())),
                         out_shardings=sh.shardings(
                             mesh, (logits_spec, c_spec)))
            lowered = fn.lower(params_abs, ins["cache"], ins["tokens"],
                               ins["pos"])
            mf = _model_flops_decode(cfg, shape)
        else:
            raise ValueError(fn_kind)

        compiled = lowered.compile()

    dt = time.time() - t0
    hlo = compiled.as_text()
    # the hierarchical level-2 sync's only collective crosses pods: its
    # bytes ride the slow DCI tier in the roofline (sync1/locals are ICI)
    # an overlapped round hides its collective behind the k local steps:
    # the roofline prices only the exposed remainder in the bottleneck
    roof = rl.analyze(name, compiled, hlo, mf, chips,
                      dci_fraction=1.0 if fn_kind == "sync2" else 0.0,
                      overlap=(fn_kind == "round" and vrl_cfg.overlap))
    # per-level compressed wire bytes of the sync payload, next to the
    # raw-payload collective bytes the HLO measures
    c1, c2 = comm_mod.resolve_pair(vrl_cfg)
    level_comp = c2 if fn_kind == "sync2" else c1
    comp_label, comp_bytes = "", 0
    if level_comp is not None and eng_spec is not None \
            and fn_kind in ("train", "sync", "sync1", "sync2", "round"):
        item = jnp.dtype(eng_spec.dtype).itemsize
        comp_label = level_comp.label()
        comp_bytes = comm_mod.wire_bytes(
            level_comp, rows=eng_spec.rows, lanes=eng_spec.lanes,
            size=eng_spec.size, itemsize=item)
    fn_label = fn_kind + ("+unroll" if unrolled else "") + \
        (f"+{tag}" if tag else "")
    res = DryrunResult(arch=arch_id, shape=shape_id, mesh=mesh_name,
                       fn=fn_label, ok=True, compile_s=dt,
                       per_device_bytes=_mem_bytes(compiled), roofline=roof,
                       compressor=comp_label, comp_bytes=comp_bytes)
    if verbose:
        try:
            print(compiled.memory_analysis())
        except Exception as e:  # noqa: BLE001
            print("memory_analysis unavailable:", e)
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        print({k: cost.get(k) for k in ("flops", "bytes accessed")})
        comp_note = ""
        if comp_label:
            raw = comm_mod.raw_bytes(eng_spec.rows, eng_spec.lanes,
                                     jnp.dtype(eng_spec.dtype).itemsize)
            comp_note = (f"  wire[{comp_label}]="
                         f"{comp_bytes/2**20:.2f} MiB ({raw/comp_bytes:.1f}x)")
        print(f"[{name}] compile {dt:.1f}s  mem/device "
              f"{res.per_device_bytes/2**30:.2f} GiB  "
              f"bottleneck={roof.bottleneck}  "
              f"terms(ms) c={roof.t_compute*1e3:.3f} "
              f"m={roof.t_memory*1e3:.3f} coll={roof.t_collective*1e3:.3f}"
              + comp_note)
    return res


FN_KINDS_BY_SHAPE = {
    "train_4k": ["train", "local", "sync"],
    "prefill_32k": ["prefill"],
    "decode_32k": ["decode"],
    "long_500k": ["decode"],
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--fn", default=None,
                    help="train|local|sync|sync1|sync2|round|prefill|decode "
                         "(default by shape; sync1/sync2 need hier_vrl_sgd; "
                         "round = one scanned comm period, state donated)")
    ap.add_argument("--all", action="store_true",
                    help="run the full arch x shape matrix")
    ap.add_argument("--unrolled", action="store_true",
                    help="unroll the layer scan (accurate roofline flops)")
    ap.add_argument("--algorithm", default="vrl_sgd",
                    choices=sorted(engine_mod.ALGO_SPECS))
    ap.add_argument("--backend", default="fused",
                    choices=["fused", "reference", "xla", "auto"],
                    help="update-math backend for the train lowerings "
                         "(fused default: artifacts reflect the production "
                         "TPU Pallas path; auto resolves by the HOST jax "
                         "backend — xla on a CPU host)")
    ap.add_argument("--k1", type=int, default=5,
                    help="hier_vrl_sgd intra-pod period")
    ap.add_argument("--k2", type=int, default=20,
                    help="hier_vrl_sgd cross-pod period")
    ap.add_argument("--comm-schedule", default=None,
                    help="stagewise round schedule for the train lowerings "
                         "(const|stagewise[:k0:rounds:k_max]|custom:kxr,..)")
    ap.add_argument("--overlap", action="store_true",
                    help="lower the OVERLAPPED round (fn=round): the sync "
                         "collective is issued at round start over the "
                         "previous boundary's transmitted positions and "
                         "folds one-round-stale; the roofline prices only "
                         "the exposed collective remainder")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="straggler miss probability per participant per "
                         "round (requires --overlap; 0 disables)")
    ap.add_argument("--round-k", type=int, default=0,
                    help="fn=round: round length to lower (a stagewise "
                         "run compiles one such executable per stage k); "
                         "0 = comm period")
    ap.add_argument("--compress", default=None,
                    help="sync-payload compressor for the train lowerings "
                         "(none|int8|topk[:rate][:noef]); artifacts gain "
                         "the compressed wire bytes next to the raw "
                         "collective bytes")
    ap.add_argument("--compress2", default=None,
                    help="override the cross-pod sync2 compressor "
                         "(hier_vrl_sgd; default: --compress)")
    ap.add_argument("--shards", type=int, default=1,
                    help="row-shard the engine state over the mesh's "
                         "'model' axis (must equal its size when > 1); "
                         "also sets the --engine-mem placement")
    ap.add_argument("--moment-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="inner-optimizer moment storage dtype")
    ap.add_argument("--sm3", action="store_true",
                    help="SM3-factored adam second moment")
    ap.add_argument("--engine-mem", action="store_true",
                    help="emit the ANALYTIC engine-state memory artifact "
                         "(eval_shape only — no compile, works at "
                         "kimi-k2-1t scale): per-buffer + per-device "
                         "bytes, the unsharded-fp32 baseline and "
                         "reduction factor, and pod-fit under v5e HBM.  "
                         "Appends one JSON line per arch to --out")
    ap.add_argument("--inner", default="adam",
                    choices=["sgd", "momentum", "adam"],
                    help="--engine-mem inner optimizer (moment buffers "
                         "are the point, so adam by default)")
    ap.add_argument("--workers", type=int, default=0,
                    help="--engine-mem worker count (0 = the arch's "
                         "single-pod mesh role)")
    ap.add_argument("--clients", type=int, default=0,
                    help="--engine-mem: also size the HOST client store "
                         "of M logical clients behind the W worker slots "
                         "(per-participant buffers x M/W + globals)")
    ap.add_argument("--gate-bytes", type=int, default=0,
                    help="--engine-mem CI gate: exit 1 if any arch's "
                         "per-device engine bytes exceed this budget")
    ap.add_argument("--worker-axes", default=None,
                    help="comma list overriding VRL worker mesh axes")
    ap.add_argument("--fsdp-axes", default=None)
    ap.add_argument("--tensor-axes", default=None)
    ap.add_argument("--seq-shard-acts", action="store_true",
                    help="Megatron-style sequence-parallel activations")
    ap.add_argument("--tag", default="", help="label for this variant")
    ap.add_argument("--no-remat", action="store_true",
                    help="disable activation checkpointing in train lowering")
    ap.add_argument("--delta-bf16", action="store_true")
    ap.add_argument("--last-only", action="store_true",
                    help="prefill emits last-position logits only")
    ap.add_argument("--two-layer", action="store_true",
                    help="2-layer unrolled calibration lowering: per-layer "
                         "roofline cost = (this run) - (scanned run); "
                         "total = scanned + (L-1) * per-layer")
    ap.add_argument("--out", default=None, help="append JSONL results here")
    args = ap.parse_args(argv)

    archs = registry.list_archs() if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(registry.INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multi"]

    if args.engine_mem:
        over_budget = []
        for arch in archs:
            art = engine_mem(arch, algorithm=args.algorithm,
                             inner=args.inner, workers=args.workers,
                             shards=args.shards,
                             moment_dtype=args.moment_dtype, sm3=args.sm3,
                             clients=args.clients)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(art) + "\n")
            if args.gate_bytes and art["per_device_bytes"] > args.gate_bytes:
                over_budget.append(
                    f"{arch}: {art['per_device_bytes']} > {args.gate_bytes}")
        if over_budget:
            print("engine-mem gate FAILED:\n  " + "\n  ".join(over_budget),
                  file=sys.stderr)
            return 1
        print(f"engine-mem: {len(archs)} arch(s) OK"
              + (f" (gate {args.gate_bytes} B/device)" if args.gate_bytes
                 else ""))
        return 0

    results = []
    failures = 0
    for arch in archs:
        for shape in shapes:
            fns = [args.fn] if args.fn else FN_KINDS_BY_SHAPE[shape]
            for multi in meshes:
                for fn_kind in fns:
                    mesh_override = {}
                    for key, val in [("worker_axes", args.worker_axes),
                                     ("fsdp_axes", args.fsdp_axes),
                                     ("tensor_axes", args.tensor_axes)]:
                        if val is not None:
                            mesh_override[key] = tuple(
                                a for a in val.split(",") if a)
                    cfg_override = {}
                    if args.seq_shard_acts:
                        cfg_override["seq_shard_acts"] = True
                    if args.two_layer:
                        cfg_override["num_layers"] = 2
                    try:
                        res = lower_one(
                            arch, shape, multi_pod=multi, fn_kind=fn_kind,
                            unrolled=args.unrolled or args.two_layer,
                            algorithm=args.algorithm,
                            backend=args.backend, k1=args.k1, k2=args.k2,
                            overlap=args.overlap, deadline=args.deadline,
                            comm_schedule=args.comm_schedule,
                            round_k=args.round_k,
                            compress=args.compress,
                            compress2=args.compress2,
                            shards=args.shards,
                            moment_dtype=args.moment_dtype, sm3=args.sm3,
                            mesh_override=mesh_override or None,
                            cfg_override=cfg_override or None,
                            tag=args.tag or ("u2" if args.two_layer else ""),
                            last_only=args.last_only,
                            no_remat=args.no_remat)
                    except Exception as e:  # noqa: BLE001
                        failures += 1
                        mesh_name = "multi" if multi else "single"
                        fl = fn_kind + ("+unroll+u2" if args.two_layer
                                        else "+unroll" if args.unrolled
                                        else "") + (f"+{args.tag}" if args.tag else "")
                        res = DryrunResult(
                            arch=arch, shape=shape, mesh=mesh_name,
                            fn=fl, ok=False, compile_s=0.0,
                            per_device_bytes=-1, roofline=None,
                            error=f"{type(e).__name__}: {e}"[:500])
                        print(f"[FAIL] {arch}/{shape}/{mesh_name}/{fn_kind}: "
                              f"{res.error}", file=sys.stderr)
                    results.append(res)
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(json.dumps(res.to_json()) + "\n")
    print(f"\ndry-run complete: {len(results) - failures}/{len(results)} OK")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
