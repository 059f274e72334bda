"""Telemetry subsystem tests (repro.obs + the driver wiring).

Covers, at three levels:
  unit      — MetricsWriter/read_metrics round-trip, schema guard, phase
              timers, the legacy-results converter shim
  engine    — the invariant monitor catches a seeded Σ Δ violation and a
              NaN-poisoned worker in ONE diagnostics pass (and does NOT
              count a dropped worker's dead rows); measured wire bytes
              match comm.rep_nbytes of an actual compressed payload
  driver    — an in-process --metrics training run emits the documented
              event stream (round/sync/diag with residuals and wire
              bytes) that report.py renders; the early-exit resume path
              evaluates the restored averaged model instead of writing
              null; a tripped --invariant-alarm feeds the --guard
              rollback; and (subprocess, 8-device mesh) building
              Engine.diagnostics leaves the compiled round's HLO at
              EXACTLY one sync all-reduce
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import VRLConfig
from repro.core import make_engine
from repro.obs import convert, report
from repro.obs import diagnostics as obs_diag
from repro.obs.metrics import (SCHEMA_VERSION, MetricsWriter, NullWriter,
                               read_metrics, run_meta)
from repro.obs.timers import PhaseTimers, percentile


# ------------------------------------------------------------------ unit
def test_writer_reader_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with MetricsWriter(path, run_meta={"arch": "x", "workers": 2}) as mw:
        assert mw.active
        mw.emit("round", t=2, r=1, loss=np.float32(1.5),
                wire_bytes=np.int64(4096))
        mw.emit("diag", t=2, drift_per_worker=jnp.arange(2.0))
        mw.emit("run_end", steps=2, avg_model_loss=1.25)
    recs = read_metrics(path)
    assert [r["event"] for r in recs] == ["run_start", "round", "diag",
                                         "run_end"]
    assert all(r["schema"] == SCHEMA_VERSION for r in recs)
    assert run_meta(recs) == {"arch": "x", "workers": 2}
    # numpy/jax values were coerced to plain JSON types
    assert recs[1]["loss"] == 1.5 and recs[1]["wire_bytes"] == 4096
    assert recs[2]["drift_per_worker"] == [0.0, 1.0]
    # wall_s is monotone from the stream open
    assert recs[0]["wall_s"] == 0.0
    assert all(recs[i]["wall_s"] <= recs[i + 1]["wall_s"]
               for i in range(len(recs) - 1))


def test_reader_rejects_newer_schema_and_garbage(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps({"schema": SCHEMA_VERSION + 1,
                             "event": "round"}) + "\n")
    with pytest.raises(ValueError, match="newer than this reader"):
        read_metrics(str(p))
    p.write_text('{"no_event": 1}\n')
    with pytest.raises(ValueError, match="'schema' and 'event'"):
        read_metrics(str(p))
    p.write_text("not json\n")
    with pytest.raises(ValueError, match="invalid JSON"):
        read_metrics(str(p))


def test_null_writer_is_inert(tmp_path):
    nw = NullWriter()
    assert not nw.active and nw.path is None
    nw.emit("round", t=1)       # must be a no-op, not an error
    nw.close()


def test_phase_timers_percentiles():
    t = PhaseTimers()
    for ms in (1, 2, 3, 4, 100):
        t.add("round", ms / 1e3)
    with t.phase("eval"):
        pass
    s = t.summary()
    assert s["round"]["n"] == 5
    assert s["round"]["p50_ms"] == pytest.approx(3.0)
    assert s["round"]["p95_ms"] == pytest.approx(100.0)
    assert s["eval"]["n"] == 1
    assert percentile([5.0], 95) == 5.0


def test_phase_spans_land_in_a_profiler_trace(tmp_path):
    """``timers.phase`` writes a host span on the profiler's clock whether
    or not a ``PhaseTimers`` samples it, and a round's
    ``StepTraceAnnotation`` sits beside it: the training loop's phases
    next to the device ops of a ``--profile-round`` trace."""
    import glob

    from repro.obs import timers as timers_mod
    t = PhaseTimers()
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.StepTraceAnnotation("round", step_num=3):
        with timers_mod.phase("data", t):
            x = jnp.arange(4.0)
        with timers_mod.phase("eval"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    pb = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(pb[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("data", "eval", "round"):
                    spans[e.name] = dict(e.stats)
    assert set(spans) == {"data", "eval", "round"}
    assert spans["round"].get("step_num") == 3
    # only the phase given a PhaseTimers was sampled
    assert list(t.summary()) == ["data"]


def test_scopemap_parses_op_names_of_compiled_hlo():
    """``obs.scopemap.parse`` keys each instruction of a compiled module's
    text (TPU layouts, ``ROOT``, a custom call's backend config) by name.
    An instruction without an ``op_name`` takes that of the loop that runs
    its computation; at the entry level it is left out."""
    from repro.obs import scopemap
    text = "\n".join([
        "HloModule jit_traced, "
        "entry_computation_layout={(f32[8]{0})->f32[8]{0}}",
        "%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {",
        '  ROOT %add.3 = f32[8]{0:T(256)} add(%param_0.1, %param_0.1), '
        'metadata={op_name="jit(traced)/engine.sync/add" source_line=4}',
        "}",
        "%body.2 (t: (s32[], f32[8])) -> (s32[], f32[8]) {",
        "  %t = (s32[], f32[8]) parameter(0)",
        "  %copy-done.6 = f32[8]{0:T(256)S(1)} copy-done(%copy-start.6)",
        '  ROOT %tuple.1 = (s32[], f32[8]) tuple(%i, %copy-done.6), '
        'metadata={op_name="jit(traced)/while/body/closed_call/model/add"}',
        "}",
        "ENTRY %main.9 (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0:T(256)} parameter(0)",
        "  %while.3 = (s32[], f32[8]) while(%t0), condition=%cond.4, "
        'body=%body.2, metadata={op_name="jit(traced)/model/while"}',
        '  %fusion.585 = f32[2,14]{1,0:T(2,128)} fusion(%p), kind=kLoop, '
        'calls=%fused_computation.1, metadata={op_type="x" '
        'op_name="jit(traced)/while/body/vmap(transpose(jvp(model)))/'
        'attention/dot_general" source_file="a.py" source_line=12}',
        '  %copy.83 = f32[8]{0:T(1,128)} copy(%fusion.585)',
        '  ROOT %vrl_sync.1 = f32[8]{0} custom-call(%copy.83), '
        'custom_call_target="tpu_custom_call", backend_config={"k": "v"}, '
        'metadata={op_name="jit(traced)/engine.sync/vrl_sync/pallas_call"}',
        "}"])
    assert scopemap.parse(text) == {
        "add.3": "jit(traced)/engine.sync/add",
        "fusion.585": "jit(traced)/while/body/vmap(transpose(jvp(model)))/"
                      "attention/dot_general",
        "vrl_sync.1": "jit(traced)/engine.sync/vrl_sync/pallas_call",
        "while.3": "jit(traced)/model/while",
        "tuple.1": "jit(traced)/while/body/closed_call/model/add",
        # the loop's own path, not its sibling's
        "copy-done.6": "jit(traced)/model/while",
        "t": "jit(traced)/model/while"}


def test_scopemap_reads_a_kernel_whose_attributes_span_lines():
    """A Pallas kernel with ``kernel_metadata`` prints its attributes over
    several lines, the ``op_name`` on the last; ``parse`` reads it there,
    and ``attention_executor`` counts the flash kernels under
    ``attention``."""
    from repro.obs import scopemap
    attn = "jit(r)/while/body/cond/branch_0_fun/attention/vmap(jit(s))"
    text = "\n".join([
        "%body.2 (t: (s32[], f32[8])) -> (s32[], f32[8]) {",
        "  %splash_mha_fwd_residuals.21 = (bf16[2,14,2048,64]{3,2,1,0}) "
        'custom-call(%a, %b), custom_call_target="tpu_custom_call", '
        "frontend_attributes={kernel_metadata={",
        '"xprof_metadata":"{\\"block_q\\": 128, \\"block_kv\\": 128}"',
        f'}}}}, metadata={{op_name="{attn}/splash_mha_fwd_residuals/'
        'pallas_call" stack_frame_id=81}, backend_config={"k": "v"}',
        "  %splash_mha_dq_no_residuals.11 = bf16[2,14,2048,64]{3,2,1,0} "
        "custom-call(%a), frontend_attributes={kernel_metadata={",
        '"xprof_metadata":"{}"',
        f'}}}}, metadata={{op_name="{attn}/splash_mha_dq_no_residuals/'
        'pallas_call"}',
        '  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f.1, '
        'metadata={op_name="jit(r)/while/body/head/dot_general"}',
        '  ROOT %tuple.1 = (s32[], f32[8]) tuple(%i, %fusion.9), '
        'metadata={op_name="jit(r)/while/body/model/add"}',
        "}"])
    paths = scopemap.parse(text)
    assert paths["splash_mha_fwd_residuals.21"] == (
        f"{attn}/splash_mha_fwd_residuals/pallas_call")
    assert paths["splash_mha_dq_no_residuals.11"] == (
        f"{attn}/splash_mha_dq_no_residuals/pallas_call")
    assert paths["fusion.9"] == "jit(r)/while/body/head/dot_general"
    assert scopemap.attention_executor(paths) == {"executor": "flash",
                                                  "kernels": 2}
    dense = {"fusion.1": "jit(r)/model/attention/dot_general"}
    assert scopemap.attention_executor(dense) == {"executor": "dense",
                                                  "kernels": 0}
    assert scopemap.attention_executor({"fusion.9": paths["fusion.9"]}) == {
        "executor": "none", "kernels": 0}


def test_round_cache_records_its_executable():
    """Each round executable ``RoundCache`` compiles becomes
    ``scopemap.latest()``, and ``op_paths`` reads its scopes."""
    from repro.core.engine import RoundCache
    from repro.obs import scopemap

    def round_step(state, xs):
        with jax.named_scope("engine.sync"):
            return state + xs.sum(0), xs.mean()

    rc = RoundCache(round_step)
    state, _ = rc(jnp.zeros(4), jnp.ones((2, 4)))
    first = scopemap.latest()
    assert first is not None
    assert any("engine.sync" in p for p in scopemap.op_paths().values())
    state, _ = rc(state, jnp.ones((2, 4)))  # the same k: nothing new
    assert scopemap.latest() is first
    rc(state, jnp.ones((3, 4)))             # a new k: its own executable
    assert scopemap.latest() is not first and rc.compiles == 2


def test_report_summarize_and_diff(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with MetricsWriter(path, run_meta={"arch": "a", "algorithm": "vrl_sgd",
                                       "workers": 2, "steps": 4}) as mw:
        mw.emit("round", t=2, r=1, k=2, loss=2.0, wire_bytes=1024)
        mw.emit("sync", t=2, r=1, wire_bytes=1024, participants=2)
        mw.emit("diag", t=2, r=1, delta_residual=1e-6, drift_sq_mean=0.5,
                zeta_sq_proxy=3.0, nonfinite_workers=0.0, alarms=[])
        mw.emit("eval", t=2, r=1, avg_model_loss=1.9, local_loss=2.0)
        mw.emit("rollback", t_fail=4, reason="non-finite state",
                back_to=2, retry=1)
        mw.emit("run_end", steps=4, final_loss=1.8, avg_model_loss=1.8,
                rounds=2, phases={"round": {"n": 2, "total_s": 1.0,
                                            "mean_ms": 500.0,
                                            "p50_ms": 500.0,
                                            "p95_ms": 600.0}})
    recs = read_metrics(path)
    text = report.summarize(recs, label="unit")
    for needle in ("run report — unit", "loss trajectory",
                   "algorithm health", "delta_residual", "rollback",
                   "wall-clock phases", "avg_model_loss=1.8"):
        assert needle in text, needle
    d = report.diff(recs, recs, labels=("L", "R"))
    assert "avg_model_loss" in d and "rollbacks" in d
    # a partial stream (no run_end — crashed run) still renders
    partial = [r for r in recs if r["event"] != "run_end"]
    assert "partial" in report.summarize(partial)


def test_converter_roundtrip(tmp_path):
    legacy = {"arch": "a", "payload_bytes": 7,
              "table": {"0.25": {"workers": 2, "bytes": 14},
                        "1.0": {"workers": 8, "bytes": 56}}}
    recs = convert.records_from_legacy(legacy, "comm_cohort")
    assert recs[0]["event"] == "run_start" and recs[0]["source"] == "bench"
    assert convert.legacy_view(recs) == legacy
    # two-level table (comm_compress shape)
    nested = {"horizons": [10], "table": {
        "ssgd/none": {"10": {"rounds": 10, "bytes": 100}},
        "vrl/none": {"10": {"rounds": 1, "bytes": 10}}}}
    recs2 = convert.records_from_legacy(nested, "comm_compress")
    keys = sorted(tuple(r["key"]) for r in recs2 if r["event"] == "bench")
    assert keys == [("ssgd/none", "10"), ("vrl/none", "10")]
    assert convert.legacy_view(recs2) == nested
    # raw row list (comm_bench shape)
    rows = [{"coll_bytes": 1}, {"coll_bytes": 2}]
    recs3 = convert.records_from_legacy(rows, "comm_bench")
    assert convert.legacy_view(recs3) == rows
    # file-to-file, both directions
    src = tmp_path / "legacy.json"
    src.write_text(json.dumps(legacy))
    canon = str(tmp_path / "canon.jsonl")
    convert.convert_file(str(src), canon)
    back = str(tmp_path / "back.json")
    convert.convert_file(canon, back)
    assert json.load(open(back)) == legacy


# ---------------------------------------------------------------- engine
def _engine(workers=4, **over):
    template = {"w": jnp.zeros((48, 16)), "b": jnp.zeros((17,))}
    cfg = VRLConfig(algorithm="vrl_sgd", comm_period=4, learning_rate=0.05,
                    weight_decay=0.0, warmup=False, update_backend="xla",
                    **over)
    eng = make_engine(cfg, template)
    p0 = {"w": jax.random.normal(jax.random.PRNGKey(0), (48, 16)),
          "b": jax.random.normal(jax.random.PRNGKey(1), (17,))}
    return eng, eng.init(p0, workers)


def test_invariant_monitor_catches_seeded_delta_violation():
    """Σ Δ = 0 is the paper's control-variate invariant: seeding +0.5
    onto one worker's Δ must raise the residual above threshold in ONE
    diagnostics pass, and check_alarms must name it."""
    eng, state = _engine()
    rec = obs_diag.to_record(jax.jit(eng.diagnostics)(state))
    assert rec["delta_residual"] < 1e-5          # clean init: float noise
    assert obs_diag.check_alarms(rec, invariant_threshold=1e-3) == []
    bad = state._replace(delta=state.delta.at[0].add(0.5))
    rec = obs_diag.to_record(jax.jit(eng.diagnostics)(bad))
    assert rec["delta_residual"] == pytest.approx(0.125)   # 0.5 / W
    alarms = obs_diag.check_alarms(rec, invariant_threshold=1e-3)
    assert len(alarms) == 1 and "sum-delta" in alarms[0]
    # the violation also shows up as control-variate dispersion
    assert rec["zeta_sq_proxy"] > 0.0


def test_invariant_monitor_catches_nan_poisoned_worker():
    eng, state = _engine()
    bad = state._replace(params=state.params.at[2, 5].set(jnp.nan))
    rec = obs_diag.to_record(jax.jit(eng.diagnostics)(bad))
    assert rec["nonfinite_workers"] == 1.0
    alarms = obs_diag.check_alarms(rec)          # fires with NO threshold
    assert len(alarms) == 1 and "non-finite" in alarms[0]
    assert "NONFINITE" in obs_diag.describe(rec)


def test_dropped_worker_nan_rows_do_not_alarm():
    """A crashed worker's rows legitimately hold garbage — membership
    masks them out of every statistic, so a dead-row NaN must not count
    as a non-finite worker (and must not poison the finite stats)."""
    eng, state = _engine(membership=True)
    mask = np.array([0.0, 1.0, 1.0, 1.0], np.float32)
    state = jax.jit(eng.set_membership)(state, mask)
    dead = state._replace(params=state.params.at[0].set(jnp.nan))
    rec = obs_diag.to_record(jax.jit(eng.diagnostics)(dead))
    assert rec["nonfinite_workers"] == 0.0
    assert np.isfinite(rec["params_rms"])
    assert np.isfinite(rec["delta_residual"])
    assert obs_diag.check_alarms(rec, invariant_threshold=1e-3) == []


def test_wire_bytes_matches_actual_compressed_payload():
    """wire_bytes_per_sync must equal rep_nbytes(compress(payload)) —
    the measured figure, not an estimate."""
    from repro.comm import compressors as cc

    for spec_str in ("int8", "topk"):
        eng, state = _engine(compress=cc.parse_compressor(spec_str))
        wire = obs_diag.wire_bytes_per_sync(eng)
        payload = jnp.linspace(-1.0, 1.0, eng.spec.padded,
                               dtype=jnp.float32
                               ).reshape(eng.spec.rows, eng.spec.lanes)
        rep = cc.compress(eng.compressors[0], payload,
                          rows_used=cc.used_rows(eng.spec.size,
                                                 eng.spec.lanes))
        assert wire["wire_bytes"] == cc.rep_nbytes(rep)
        assert wire["wire_bytes"] < wire["raw_bytes"]
        assert wire["wire_bytes2"] is None       # flat engine
    assert obs_diag.wire_bytes_per_sync(None) is None


# ---------------------------------------------------------------- driver
SMOKE = ["--arch", "qwen2-0.5b", "--smoke", "--workers", "2",
         "--batch", "2", "--seq", "32", "--k", "2", "--lr", "0.02",
         "--backend", "xla"]


def test_training_run_emits_documented_stream(tmp_path):
    from repro.launch import train

    m = str(tmp_path / "m.jsonl")
    lo = str(tmp_path / "loss.json")
    train.main(SMOKE + ["--steps", "4", "--log-every", "1",
                        "--metrics", m, "--loss-out", lo])
    recs = read_metrics(m)
    meta = run_meta(recs)
    assert meta["algorithm"] == "vrl_sgd" and meta["workers"] == 2
    assert meta["wire"]["wire_bytes"] > 0        # measured sync payload
    rounds = [r for r in recs if r["event"] == "round"]
    diags = [r for r in recs if r["event"] == "diag"]
    syncs = [r for r in recs if r["event"] == "sync"]
    assert len(rounds) == 2 and len(syncs) == 2 and len(diags) == 2
    assert all(r["wire_bytes"] == meta["wire"]["wire_bytes"]
               for r in rounds)
    assert syncs[0]["participants"] == 2
    for d in diags:                 # the paper-grounded health fields
        for key in ("delta_residual", "drift_sq_mean", "zeta_sq_proxy",
                    "params_rms", "nonfinite_workers"):
            assert np.isfinite(d[key]), key
        assert d["alarms"] == []
    end = recs[-1]
    assert end["event"] == "run_end" and end["steps"] == 4
    assert np.isfinite(end["avg_model_loss"])
    assert end["phases"]["round"]["n"] == 2
    # the CPU lowering runs the dense attention core
    assert end["attention"] == {"executor": "dense", "kernels": 0}
    # --loss-out and the stream agree, and the reporter renders it
    assert json.load(open(lo))["avg_model_loss"] == end["avg_model_loss"]
    text = report.summarize(recs)
    assert "delta_residual" in text and "communication:" in text


def test_early_exit_resume_evaluates_restored_model(tmp_path):
    """Regression: resuming past --steps used to dump
    avg_model_loss: null without ever evaluating the restored model."""
    from repro.launch import train

    ck = str(tmp_path / "ck")
    train.main(SMOKE + ["--steps", "4", "--ckpt", ck,
                        "--ckpt-every", "2"])
    lo = str(tmp_path / "loss.json")
    m = str(tmp_path / "m.jsonl")
    rc = train.main(SMOKE + ["--steps", "2", "--ckpt", ck,
                             "--resume", "auto", "--loss-out", lo,
                             "--metrics", m])
    assert rc == 0
    out = json.load(open(lo))
    assert out["steps"] == 4                     # the checkpoint's step
    assert isinstance(out["avg_model_loss"], float)
    assert np.isfinite(out["avg_model_loss"])    # was None before the fix
    recs = read_metrics(m)
    assert [r["event"] for r in recs] == ["run_start", "restore",
                                          "run_end"]
    assert recs[-1]["avg_model_loss"] == out["avg_model_loss"]


def test_invariant_alarm_feeds_guard_rollback(tmp_path, capsys):
    """Under a lossy sync compressor Σ Δ is genuinely nonzero (the
    EF-bounded rebuild bias), so a near-zero --invariant-alarm must trip
    on the first diagnosed round and drive the --guard rollback path to
    exhaustion — proving the monitor is wired into the same machinery as
    the loss/finiteness guard."""
    from repro.launch import train

    m = str(tmp_path / "m.jsonl")
    with pytest.raises(SystemExit, match="still diverged"):
        train.main(SMOKE + ["--steps", "2", "--compress", "topk",
                            "--guard", "--max-retries", "1",
                            "--invariant-alarm", "1e-9",
                            "--log-every", "1", "--metrics", m])
    out = capsys.readouterr().out
    assert "invariant alarm" in out and "rolled back" in out
    rbs = [r for r in read_metrics(m) if r["event"] == "rollback"]
    assert len(rbs) == 2 and rbs[-1].get("aborted") is True
    assert all("invariant alarm" in r["reason"] for r in rbs)


def test_diag_flags_need_an_engine():
    from repro.launch import train

    with pytest.raises(SystemExit, match="--backend reference has none"):
        train.main(["--arch", "qwen2-0.5b", "--smoke", "--steps", "2",
                    "--workers", "2", "--batch", "2", "--seq", "32",
                    "--backend", "reference", "--diag"])


@pytest.mark.parametrize("flags, msg", [
    (["--invariant-alarm", "-1"], "--invariant-alarm must be >= 0"),
    (["--profile-round", "2"], "--profile-round needs --profile-dir"),
    (["--profile-round", "2", "--profile-dir", "/tmp/x", "--no-round"],
     "drop\\s+--no-round"),
])
def test_bad_obs_flags_exit_with_named_message(flags, msg):
    from repro.launch import train

    with pytest.raises(SystemExit, match=msg):
        train.main(["--smoke", "--steps", "4"] + flags)


# ------------------------------- HLO contract with diagnostics enabled
HLO_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import VRLConfig
    from repro.core import make_engine

    mesh = jax.make_mesh((8,), ("data",), devices=jax.devices())
    template = {"w": jnp.zeros((64, 16)), "b": jnp.zeros((33,))}
    cfg = VRLConfig(algorithm="vrl_sgd", comm_period=4, learning_rate=0.05,
                    weight_decay=0.0, warmup=False, update_backend="xla")
    eng = make_engine(cfg, template, mesh=mesh, worker_axes=("data",))
    p0 = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 16)),
          "b": jax.random.normal(jax.random.PRNGKey(1), (33,))}
    state = eng.init(p0, 8)

    def shard(x):
        nd = getattr(x, "ndim", 0)
        spec = P("data", None, None) if nd == 3 else P(*([None] * nd))
        return jax.device_put(x, NamedSharding(mesh, spec))

    state = jax.tree.map(shard, state)

    def count_ar(hlo):
        return len(re.findall(r"all-reduce(?:-start)?\\(", hlo))

    # the diagnostics jit compiles and runs on the mesh-sharded state
    diag = jax.device_get(jax.jit(eng.diagnostics)(state))
    out = {"diag_keys": sorted(diag.keys()),
           "delta_residual": float(diag["delta_residual"]),
           "drift_len": int(diag["drift_per_worker"].size)}

    # ... and the compiled ROUND is untouched: still exactly ONE sync
    # all-reduce for the k scanned local steps
    gk = jax.tree.map(lambda x: jnp.stack([jnp.sin(3.0 * x + t) + 0.1 * x
                                           for t in range(4)]),
                      eng.params_tree(state))
    hlo_round = jax.jit(eng.round_step, donate_argnums=(0,)
                        ).lower(state, gk).compile().as_text()
    out["round_all_reduce"] = count_ar(hlo_round)
    print(json.dumps(out))
""")


def test_round_hlo_one_all_reduce_with_diagnostics_built():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", HLO_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["round_all_reduce"] == 1          # the contract holds
    assert out["delta_residual"] < 1e-5
    assert out["drift_len"] == 8                 # one entry per worker
    for key in ("delta_residual", "drift_sq_mean", "zeta_sq_proxy",
                "params_rms", "nonfinite_workers"):
        assert key in out["diag_keys"], key
