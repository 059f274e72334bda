#!/usr/bin/env python3
"""Chip smoke: the VRL-SGD training round at published widths on a TPU.

Runs ``repro.launch.train.main`` — the driver a user calls — in this
process, for qwen2-0.5b at its published widths (24 layers, d_model 896,
14/2 heads, d_ff 4864, vocab 151,936; random weights from seed 0): 4 local
steps as 2 rounds of k=2, one sequence of 512 tokens per worker per step.
It runs once with the backend ``auto`` picks on a TPU (the compiled fused
Pallas kernels) and once with ``--backend xla`` on the same data, then
checks that

  * JAX's first device is a TPU;
  * ``auto`` resolved to the fused kernels, compiled and not interpreted;
  * every round loss and the final averaged-model loss are finite;
  * the fused and xla round losses agree to ``RTOL``;
  * no diagnostics record has a non-finite worker or an alarm;
  * with ``--chips 4``: the four workers sit on four distinct TPUs, one
    each, and after every sync the workers coincide (zero drift) with
    Σ Δ = 0 to fp32 rounding.

Only then is the last line of stdout
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure exits non-zero without that line.

    python chip_smoke.py            # one chip, one worker
    python chip_smoke.py --chips 4  # one worker per chip over a mesh

Compiled programs go to the persistent cache (``repro.launch.cache``), so
a second run in the same checkout skips most of the compile.  The times
printed are a smoke's, from one short run: they are not a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen2-0.5b"
STEPS, K = 4, 2
ARGV = ["--arch", ARCH, "--algorithm", "vrl_sgd", "--batch", "1",
        "--seq", "512", "--k", str(K), "--steps", str(STEPS), "--lr", "0.05",
        "--log-every", "1", "--diag"]
RTOL = 1e-3             # fused vs xla, per round loss
# Σ Δ over W workers is a sum of W fp32 terms: allow a few ulps of the
# largest |Δ|, which sqrt(W · zeta_sq_proxy) bounds from above
DELTA_ULPS = 16 * 2.0 ** -23


class SmokeFailure(Exception):
    pass


def require_tpu(count: int):
    """The device check: JAX's first device is a TPU and there are at
    least ``count`` of them.  Returns ``jax.devices()``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is "
                           f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < count:
        raise SmokeFailure(f"--chips {count} needs {count} TPUs, JAX "
                           f"sees {len(devs)}")
    return devs


def _finite(x) -> bool:
    return x is not None and math.isfinite(x)


def train_once(backend: str, workers: int, out: Path) -> dict:
    """One ``train.main`` run; returns its metrics stream, parsed."""
    from repro.launch import train
    from repro.obs.metrics import read_metrics
    argv = ARGV + ["--workers", str(workers),
                   "--metrics", str(out / f"{backend}.jsonl"),
                   "--loss-out", str(out / f"{backend}.json")]
    if backend != "auto":
        argv += ["--backend", backend]
    if workers > 1:
        argv += ["--mesh-grid"]
    print(f"chip_smoke: train.main {' '.join(argv)}", flush=True)
    t0 = time.perf_counter()
    try:
        rc = train.main(argv)
    except SystemExit as e:
        rc = e.code
    if rc:
        raise SmokeFailure(f"train.main ({backend}) exited with {rc}")
    wall = time.perf_counter() - t0
    recs = read_metrics(str(out / f"{backend}.jsonl"))
    gc.collect()        # drop the run's device buffers before the next
    ev = lambda name: [r for r in recs if r["event"] == name]  # noqa: E731
    run = {"meta": next(r["meta"] for r in recs
                        if r["event"] == "run_start"),
           "rounds": ev("round"), "diags": ev("diag"),
           "end": (ev("run_end") or [{}])[-1], "wall": wall}
    check_run(backend, workers, run)
    return run


def check_run(backend: str, workers: int, run: dict) -> None:
    meta, rounds, diags = run["meta"], run["rounds"], run["diags"]
    want = "fused" if backend == "auto" else backend
    if meta.get("resolved_backend") != want:
        raise SmokeFailure(f"{backend}: resolved backend "
                           f"{meta.get('resolved_backend')!r}, want {want!r}")
    if want == "fused" and meta.get("interpret") is not False:
        raise SmokeFailure(f"fused engine built with interpret="
                           f"{meta.get('interpret')!r}; the kernels must "
                           f"compile on the TPU")
    if len(rounds) != STEPS // K:
        raise SmokeFailure(f"{backend}: {len(rounds)} round records, "
                           f"want {STEPS // K}")
    losses = [r["loss"] for r in rounds] + [run["end"].get("avg_model_loss")]
    if not all(_finite(x) for x in losses):
        raise SmokeFailure(f"{backend}: non-finite loss in {losses}")
    if len(diags) != len(rounds):
        raise SmokeFailure(f"{backend}: {len(diags)} diag records for "
                           f"{len(rounds)} rounds")
    for d in diags:
        if d.get("nonfinite_workers") != 0 or d.get("alarms"):
            raise SmokeFailure(f"{backend}: round {d.get('r')} diag "
                               f"nonfinite_workers="
                               f"{d.get('nonfinite_workers')} alarms="
                               f"{d.get('alarms')}")
    if workers > 1:
        placed = meta.get("worker_devices")
        flat = [i for ids in placed for i in ids]
        if (len(placed) != workers or any(len(ids) != 1 for ids in placed)
                or len(set(flat)) != workers):
            raise SmokeFailure(f"{backend}: workers are not one per chip: "
                               f"worker_devices={placed}")
        for d in diags:
            bound = DELTA_ULPS * math.sqrt(workers * d["zeta_sq_proxy"])
            if d["drift_max"] != 0.0 or not d["delta_residual"] <= bound:
                raise SmokeFailure(
                    f"{backend}: round {d['r']} after its sync: drift_max="
                    f"{d['drift_max']!r} (want 0), delta_residual="
                    f"{d['delta_residual']!r} (bound {bound:.3g})")


def compare(fused: dict, xla: dict) -> None:
    pairs = [(a["loss"], b["loss"]) for a, b in zip(fused["rounds"],
                                                   xla["rounds"])]
    pairs.append((fused["end"]["avg_model_loss"],
                  xla["end"]["avg_model_loss"]))
    for a, b in pairs:
        if abs(a - b) > RTOL * abs(b):
            raise SmokeFailure(f"fused vs xla losses differ beyond rtol "
                               f"{RTOL}: {pairs}")
    print(f"chip_smoke: fused vs xla losses (rounds..., avg model) "
          f"{pairs} agree to rtol {RTOL}")


def report(tag: str, run: dict) -> None:
    secs = [r["seconds"] for r in run["rounds"]]
    steady = min(secs[1:])
    print(f"chip_smoke: {tag}: first round {secs[0]:.3f} s (compile "
          f"included), compile ~{secs[0] - steady:.3f} s, smoke round time "
          f"{steady:.3f} s (k={K}; one short run, not a benchmark), "
          f"train.main wall {run['wall']:.1f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: one worker on one chip.  4: one worker per "
                         "chip, synced by one all-reduce over the mesh")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="directory for the runs' metrics streams")
    args = ap.parse_args(argv)
    try:
        from repro.configs import registry
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repo's code next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    try:
        devs = require_tpu(args.chips)
        import jax
        cache = enable_compile_cache()
        cfg = registry.get_arch(ARCH)
        print(f"chip_smoke: device_kind={devs[0].device_kind} "
              f"count={len(devs)} chips_used={args.chips} "
              f"compile_cache={cache}")
        print(f"chip_smoke: {ARCH} at published widths: {cfg.num_layers} "
              f"layers, d_model {cfg.d_model}, heads {cfg.num_heads}/"
              f"{cfg.num_kv_heads}, d_ff {cfg.d_ff}, vocab "
              f"{cfg.vocab_size}")
        out = Path(args.out) / f"chips{args.chips}"
        out.mkdir(parents=True, exist_ok=True)
        fused = train_once("auto", args.chips, out)
        print(f"chip_smoke: params {fused['meta']['n_params']:,} per "
              f"worker, workers on devices "
              f"{fused['meta']['worker_devices']}")
        report("fused", fused)
        xla = train_once("xla", args.chips, out)
        report("xla", xla)
        compare(fused, xla)
        peak = max(d.memory_stats()["peak_bytes_in_use"]
                   for d in devs[:args.chips])
        print(f"chip_smoke: peak_bytes_in_use {peak:,} "
              f"({peak / 2**30:.2f} GiB) on the busiest chip")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
