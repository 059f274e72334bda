"""One run of one cell: set-up, the measured window, an optional traced
segment, then the comparison with the plain reference.

The window drives the program as ``repro.launch.train`` does with round
execution and no ``--metrics``: ``make_train_step`` with ``remat=True``,
the default parameter dtype, and the ``VRLConfig`` that the traffic
file's ``vrl`` block gives over ``DEFAULTS`` (VRL-SGD over the inner SGD,
``update_backend="auto"``, a blocking sync, no warm-up period); one round
(k scanned local steps and the sync) per dispatch through
``core.engine.RoundCache``, each round's tokens sent with ``jnp.asarray``
and their labels made with ``jnp.roll``.  With W > 1 the workers sit one
per chip on ``launch.mesh.make_engine_mesh(W)``, as ``--mesh-grid``
builds it.  No round is blocked on except the one before the newest, so
at most one round waits behind the running one.

Set-up builds the round and its state once, from weights this benchmark
makes, and drives the first ``follow`` rounds through the window's own
call and feed; a read-out of those rounds is kept.  The same state then
runs the window.  After the window, and after the peak memory is read,
the program's state is freed and the reference follows the same rounds
from the same weights.  The read-out's time is the check's, not set-up's.
"""
from __future__ import annotations

import gc
import math
import sys
import tempfile
import time

import numpy as np

from benchlib import counts, files, traffic as traffic_mod


class CompileCounter:
    """Counts JAX's trace and compile events while ``active``."""

    def __init__(self):
        import jax
        self.active, self.events = False, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.active and name.startswith("/jax/core/compile/"):
            self.events.append(name)


def memory_stat(devices, key: str = "peak_bytes_in_use") -> int:
    """``key`` of the fullest chip's memory statistics: by default the
    peak bytes in use (0 where the backend keeps no count, as the CPU's
    does not)."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


# The program's settings a traffic file may change, and what they are
# unless it does: what ``launch/train.py`` runs without flags, with no
# warm-up period.  ``k`` and ``lr`` come from the traffic's own keys.
DEFAULTS = dict(algorithm="vrl_sgd", inner_optimizer="sgd", warmup=False,
                update_backend="auto")


def vrl_config(t: dict):
    """The ``VRLConfig`` of a traffic file: its ``vrl`` block over
    ``DEFAULTS``, parsed as ``launch/train.py`` parses its flags.  The
    block may hold any ``VRLConfig`` field; ``compress``, ``compress2``
    and ``comm_schedule`` are the flags' strings, ``engine`` the
    ``EngineConfig`` fields, and ``hier`` ``{"pods", "k1", "k2"}``."""
    from repro.comm.compressors import parse_compressor
    from repro.configs.base import EngineConfig, HierConfig, VRLConfig
    from repro.core.schedule import parse_schedule
    kw = dict(DEFAULTS, **t.get("vrl", {}))
    engine = dict(dict(block=0, round_scan=True, shards=1),
                  **kw.pop("engine", {}))
    hier = kw.pop("hier", None)
    if hier is not None:
        hier = HierConfig(k1=hier["k1"], k2=hier["k2"],
                          grid=(hier["pods"], t["workers"] // hier["pods"]))
    for name in ("compress", "compress2"):
        if kw.get(name):
            kw[name] = parse_compressor(kw[name])
    if kw.get("comm_schedule"):
        kw["comm_schedule"] = parse_schedule(kw["comm_schedule"], t["k"])
    return VRLConfig(comm_period=t["k"], learning_rate=t["lr"],
                     engine=EngineConfig(**engine), hier=hier, **kw)


def build(cfg: dict, t: dict, devices):
    """The program's round, exactly as ``launch/train.py`` builds it."""
    from repro.configs.base import ModelConfig
    from repro.launch import mesh as mesh_mod
    from repro.train.train_loop import make_train_step
    model = ModelConfig(**cfg["program"])
    vrl = vrl_config(t)
    mesh, worker_axes = None, ("data",)
    if t["workers"] > 1:
        mesh = mesh_mod.make_engine_mesh(
            t["workers"], shards=vrl.engine.shards,
            pods=vrl.hier.grid[0] if vrl.hier else 0,
            shard_axis=vrl.engine.shard_axis, devices=devices)
        worker_axes = ("pod", "data")
    bundle = make_train_step(model, vrl, remat=True, mesh=mesh,
                             worker_axes=worker_axes)
    return bundle, vrl


def make_readout(spec, p0_fn):
    """A jitted read-out of the program's flat state after a round:
    per worker and leaf, the norm of (params - params0) and of delta; the
    per-leaf norms of sum_i delta_i; and the largest difference between
    two workers' parameters.  ``p0_fn(key)`` remakes params0 inside the
    call, so the starting weights hold no memory of their own beside the
    state."""
    import jax
    import jax.numpy as jnp

    def leaf_sq(vec, ref_leaves=None):
        out = []
        for i, l in enumerate(spec.leaves):
            x = vec[l.offset:l.offset + l.size]
            if ref_leaves is not None:
                x = x - ref_leaves[i].reshape(-1)
            out.append(jnp.sum(jnp.square(x.astype(jnp.float32))))
        return jnp.sqrt(jnp.stack(out))

    def readout(params, delta, key):
        p0l = jax.tree.leaves(p0_fn(key))
        flat_p = params.reshape(params.shape[0], -1)
        upd = jax.vmap(lambda v: leaf_sq(v, p0l))(flat_p)
        drift = jnp.max(jnp.max(params, axis=0) - jnp.min(params, axis=0))
        if isinstance(delta, tuple):
            z = jnp.zeros((params.shape[0], len(spec.leaves)))
            return upd, z, z[0], drift
        flat_d = delta.reshape(delta.shape[0], -1)
        dn = jax.vmap(leaf_sq)(flat_d)
        dsum = leaf_sq(jnp.sum(flat_d, axis=0))
        return upd, dn, dsum, drift

    return jax.jit(readout)


class Program:
    """One cell's program and its reference, built once: the round as
    ``build`` makes it behind ``core.engine.RoundCache``; the reference
    module that the traffic file (or else the configuration) names, which
    has to follow the program's settings; one jitted call that makes the
    engine's state from a seed; the read-out; and the comparison.  A
    reference module may bring its own ``make_readout`` and ``compare``
    for a state or numbers of another shape."""

    def __init__(self, cfg: dict, t: dict, devices):
        import functools

        import jax
        from repro.core import engine as engine_mod
        self.t, self.devices = t, list(devices)
        self.ref = ref = files.reference(cfg, t)
        self.m = ref.dims(cfg)
        self.bundle, self.vrl = build(cfg, t, self.devices)
        try:
            ref.check(self.vrl)
        except ValueError as e:
            raise files.BenchError(f"the reference cannot follow the "
                                   f"program: {e}") from None
        eng = self.bundle.engine
        p0_fn = functools.partial(ref.params_from_key, self.m)
        _check_leaf_order(eng.spec, jax.eval_shape(p0_fn, ref.seed_key(0)))
        # The weights are made inside the call that builds the state, so
        # set-up never holds them beside the state's buffers.
        self.make_state = jax.jit(
            lambda key: eng.init(p0_fn(key), t["workers"]))
        self.round_fn = engine_mod.RoundCache(self.bundle.round_step)
        self.readout = getattr(ref, "make_readout", make_readout)(
            eng.spec, p0_fn)
        self.compare = getattr(ref, "compare", compare)

    def reference(self, **kw):
        """The reference over this cell's workers and chips; ``kw`` gives
        the control's dtype or a planted fault."""
        return self.ref.Reference(self.m, self.vrl,
                                  workers=self.t["workers"],
                                  devices=self.devices, **kw)

    def follow(self, feed, seed: int):
        """Set-up's state and its first rounds: the engine's state from
        this benchmark's weights, then ``follow`` rounds through the
        window's call and feed, each read out for the comparison.
        Returns (state, read-out, the rounds' loss arrays, seconds the
        read-out took)."""
        key = self.ref.seed_key(seed)
        state = self.make_state(key)
        prog = {"losses": [], "update": [], "delta": [], "dsum": [],
                "drift": [], "round_s": []}
        all_losses, check_s = [], 0.0
        for r in range(self.t["follow"]):
            r0 = time.perf_counter()
            state, losses = self.round_fn(state, *feed(r))
            all_losses.append(losses)
            losses.block_until_ready()
            c0 = time.perf_counter()
            prog["round_s"].append(c0 - r0)
            upd, dn, dsum, drift = self.readout(state.params, state.delta,
                                                key)
            prog["losses"] += [float(x) for x in np.asarray(losses)]
            prog["update"].append(np.asarray(upd))
            prog["delta"].append(np.asarray(dn))
            prog["dsum"].append(np.asarray(dsum))
            prog["drift"].append(float(drift))
            check_s += time.perf_counter() - c0
        return state, prog, all_losses, check_s


def _check_leaf_order(spec, p0):
    import jax
    got = [tuple(x.shape) for x in jax.tree.leaves(p0)]
    want = [tuple(l.shape) for l in spec.leaves]
    if got != want:
        raise files.BenchError(f"the reference's leaves {got} do not line "
                               f"up with the program's flat layout {want}")


def run(spec: dict, cell: dict, seed: int, seconds: float, trace: bool, *,
        t0: float, devices, cfg: dict | None = None,
        traffic: dict | None = None, limits: dict | None = None,
        log=print) -> dict:
    """One run of ``cell``; returns the result line's object.  ``cfg``,
    ``traffic`` and ``limits`` default to the cell's files (tests pass
    small ones)."""
    import jax
    import jax.numpy as jnp

    cfg = cfg or files.config(cell)
    t = traffic_mod.check(traffic or files.traffic(cell))
    limits = limits or files.limits(cell)
    chips = cell["chips"]
    devs = list(devices)[:chips]
    if t["workers"] > 1 and t["workers"] != chips:
        raise files.BenchError("a cell with W > 1 puts one worker per chip")
    counter = CompileCounter()

    p = Program(cfg, t, devs)
    pool = traffic_mod.token_pool(t, p.m.vocab, seed)

    def feed(r):
        toks = jnp.asarray(pool[r % t["rounds"]])
        return toks, jnp.roll(toks, -1, axis=-1)

    state, prog, all_losses, check_s = p.follow(feed, seed)
    round_fn = p.round_fn
    setup_s = time.perf_counter() - t0 - check_s
    log(f"bench: set-up {setup_s:.3f} s (check read-out {check_s:.3f} s "
        f"left out), round compiles {round_fn.compiles}, set-up rounds "
        f"{prog['round_s']} s; peak {memory_stat(devs)} B, in use "
        f"{memory_stat(devs, 'bytes_in_use')} B, of it in arrays "
        f"{sum(x.nbytes for x in jax.live_arrays())} B")

    # ------------------------------------------------------------ window
    # Set-up leaves a large heap (JAX, the traced model); a full
    # collection inside the window would walk all of it.  Freezing it
    # keeps the window's collections to what the window allocates.
    gc.collect()
    gc.freeze()
    gc_before = [g["collections"] for g in gc.get_stats()]
    compiles_before = round_fn.compiles
    counter.active = True
    r, done, pending, ends = t["follow"], 0, None, []
    w_start = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("data"):
            toks, labels = feed(r)
        with jax.profiler.TraceAnnotation("dispatch"):
            state, losses = round_fn(state, toks, labels)
        r += 1
        all_losses.append(losses)
        if pending is not None:
            with jax.profiler.TraceAnnotation("wait"):
                pending.block_until_ready()
            done += 1
            ends.append(time.perf_counter())
            if ends[-1] - w_start >= seconds:
                break
        pending = losses
    losses.block_until_ready()
    done += 1
    w_end = time.perf_counter()
    counter.active = False
    collections = [g["collections"] - b
                   for g, b in zip(gc.get_stats(), gc_before)]
    gc.unfreeze()
    gaps = np.diff([w_start] + ends + [w_end])
    window_compiles = len(counter.events) + round_fn.compiles - compiles_before
    tokens_per_s = done * traffic_mod.tokens_per_round(t) / (w_end - w_start)
    mem = memory_stat(devs)
    log(f"bench: window {w_end - w_start:.3f} s, {done} rounds, "
        f"{tokens_per_s:.1f} tokens/s, peak {mem} B, compile events "
        f"{counter.events}; between round ends median "
        f"{np.median(gaps):.4f} s, max {np.max(gaps):.4f} s (round "
        f"{int(np.argmax(gaps))}); garbage collections by generation "
        f"{collections}")

    result_device = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind,
                     "count": len(jax.devices()),
                     "memory_peak_bytes": mem}
    ctx = None
    if trace:
        ctx = traced_segment(state, round_fn, feed, r, t, cfg, devs,
                             (w_end - w_start) / done, log)
        ctx.update(tokens_per_s=tokens_per_s, chips=chips)
        result_device["busy_s"] = float(np.mean(list(
            ctx["busy_s"].values())))
        result_device["window_s"] = ctx["window_s"]
    nonfinite = sum(int(not np.all(np.isfinite(np.asarray(x))))
                    for x in all_losses)

    # --------------------------------------------- free, then reference
    compare_fn, refr, ref, m = p.compare, p.reference(), p.ref, p.m
    del state, losses, pending, round_fn, p, all_losses
    gc.collect()
    r0 = time.perf_counter()
    rounds = [pool[i] for i in range(t["follow"])]
    readings = refr.run(ref.init_params(m, seed), rounds)
    log(f"bench: reference {time.perf_counter() - r0:.3f} s")
    numbers = compare_fn(prog, readings, t)
    numbers["nonfinite_rounds"] = nonfinite
    numbers["window_compiles"] = window_compiles
    skip = limits.get("not_compared", {})
    checks = {k: {"value": v, "limit": limits[k]["limit"]}
              for k, v in numbers.items() if k in limits}
    missing = sorted(set(numbers) - set(limits) - set(skip))
    correct = not missing and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    for k, v in numbers.items():
        if k in skip:
            print(f"reading {k}: {v!r} (not compared)", file=sys.stderr)
    if missing:
        print(f"check: no limit for {missing}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)

    metrics = {}
    if not trace:
        values = {"tokens_per_s": tokens_per_s, "peak_hbm_gib": mem / 2**30,
                  "setup_s": setup_s}
        for mt in files.cell_metrics(spec, cell["name"], "end_to_end"):
            metrics[mt["name"]] = {"value": values[mt["name"]],
                                   "unit": mt["unit"]}
    else:
        for mt in files.cell_metrics(spec, cell["name"], "per_layer"):
            v = files.metric_reader(mt["name"])(ctx)
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
    out = {"correct": bool(correct), "attempted": done + t["follow"],
           "failed": nonfinite, "metrics": metrics, "device": result_device}
    if trace:
        out["breakdown"] = {"device_ops": ctx["top_ops"],
                            "idle_gaps": ctx["idle_gaps"]}
    out["checks"] = checks
    return out


def traced_segment(state, round_fn, feed, r, t, cfg, devs, round_s,
                   log) -> dict:
    """Trace a few more rounds of the same program, after the window:
    at least 3, and enough for about two seconds."""
    import jax
    from benchlib import xtrace
    n = max(3, math.ceil(2.0 / max(round_s, 1e-3)))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("window"):
            pending = None
            for i in range(n):
                with jax.profiler.TraceAnnotation("data"):
                    toks, labels = feed(r + i)
                with jax.profiler.TraceAnnotation("dispatch"):
                    state, losses = round_fn(state, toks, labels)
                if pending is not None:
                    with jax.profiler.TraceAnnotation("wait"):
                        pending.block_until_ready()
                pending = losses
            with jax.profiler.TraceAnnotation("wait"):
                losses.block_until_ready()
        jax.profiler.stop_trace()
        tr = xtrace.load(tmp)
    used = {str(d.id) for d in devs}
    tr["devices"] = {d: ops for d, ops in tr["devices"].items() if d in used}
    log(f"bench: traced {n} rounds, {sum(len(v) for v in tr['devices'].values())} "
        f"device ops")
    return {"trace": tr, "rounds_traced": n, "k": t["k"],
            "workers": t["workers"], "config": cfg, "traffic": t,
            "busy_s": xtrace.busy_s(tr), "window_s": xtrace.window_s(tr),
            "top_ops": xtrace.top_ops(tr), "idle_gaps": xtrace.idle_gaps(tr),
            "flops_per_token": counts.train_flops_per_token(cfg, t["seq"]),
            "peaks": files.peaks(devs[0].device_kind)}


# ------------------------------------------------------------ comparison
def norm_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    """Worst leaf's |‖prog‖ - ‖ref‖| over the larger of that leaf's
    reference norm and the median leaf's (leaves with ``keep`` only)."""
    if not keep.any():
        return 0.0
    med = float(np.median(ref[keep]))
    den = np.maximum(ref, med)
    return float(np.max(np.abs(prog - ref)[keep] / den[keep]))


def moved(ref_update: np.ndarray) -> np.ndarray:
    """Leaves the reference moves: a leaf whose change is under a
    thousandth of the median leaf's moves by round-off alone in the
    program (a key's bias under softmax has no gradient)."""
    return ref_update >= 1e-3 * np.median(ref_update)


def delta_sum_share(sum_norms: np.ndarray, worker_norms: np.ndarray
                    ) -> float:
    """|sum_i delta_i| / sum_i |delta_i|, from per-leaf norms: the sum's
    (leaves,) and each worker's (W, leaves)."""
    denom = float(np.sum(np.sqrt(np.sum(worker_norms ** 2, axis=1))))
    num = float(np.sqrt(np.sum(sum_norms ** 2)))
    return num / denom if denom > 0 else 0.0


def as_program(rd) -> dict:
    """A reference variant's readings (the control, or a planted fault)
    in the shape of the program's read-out, to compare as the program."""
    return {"losses": list(rd.losses),
            "update": [np.asarray(u)[None] for u in rd.update_norms],
            "delta": list(rd.delta_norms), "dsum": list(rd.delta_sum_norms),
            "drift": list(rd.drift)}


def compare(prog: dict, refr, t: dict) -> dict:
    """The numbers compared with the cell's limits."""
    ref_l = np.asarray(refr.losses)
    got_l = np.asarray(prog["losses"])
    out = {"loss": float(np.max(np.abs(got_l - ref_l) / np.abs(ref_l)))}
    keep = moved(refr.update_norms[0])
    for i, (pu, ru) in enumerate(zip(prog["update"], refr.update_norms)):
        name = "update" if i == 0 else f"update{i + 1}"
        out[name] = max(norm_gap(pu[w], ru, keep)
                        for w in range(pu.shape[0]))
    if t["workers"] > 1:
        gaps = []
        for pd, rd in zip(prog["delta"], refr.delta_norms):
            for w in range(pd.shape[0]):
                kd = rd[w] >= 1e-3 * np.median(rd[w])
                gaps.append(norm_gap(pd[w], rd[w], kd))
        out["delta"] = max(gaps)
        out["delta_sum"] = max(delta_sum_share(s, d) for s, d in
                               zip(prog["dsum"], prog["delta"]))
        out["drift"] = max(prog["drift"])
    return out
