#!/usr/bin/env python3
"""Chip benchmark of the VRL-SGD training round.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPUs of this machine: builds
the program's round as ``repro.launch.train`` does, warms it up, measures
for ``--seconds``, checks what the timed path produced against the plain
float32 reference, and prints one JSON object as the last line of
standard output.  With ``--trace 0`` its metrics are the cell's
end-to-end metrics; with ``--trace 1`` a few more rounds are traced after
the window and the metrics are the cell's per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.  Compiled programs are kept in ``.jax_cache`` at the
root of the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def require_tpu(chips: int):
    """JAX's first device is a TPU and there are at least ``chips``."""
    import jax
    from benchlib.files import BenchError
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {devs[0].platform} "
                         f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} TPUs, JAX sees "
                         f"{len(devs)}")
    return devs


def enable_cache():
    """JAX's persistent compile cache in ``<checkout>/.jax_cache`` (the
    program's own rule), holding every program, however quick to
    compile, so that a second run compiles nothing."""
    import jax
    from repro.launch.cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a non-negative whole number")
    try:
        from benchlib import cell as cell_mod, files
    except ImportError as e:
        print(f"bench: cannot import the benchmark's library ({e})",
              file=sys.stderr)
        return 2
    try:
        spec = files.benchmark()
        cell = files.cell(spec, args.workload)
        devs = require_tpu(cell["chips"])
        cache = enable_cache()
        print(f"bench: {args.workload} seed {args.seed} on "
              f"{len(devs)} x {devs[0].device_kind}, compile cache {cache}",
              flush=True)
        out = cell_mod.run(spec, cell, args.seed, args.seconds,
                           bool(args.trace), t0=T0, devices=devs,
                           log=lambda s: print(s, flush=True))
    except (files.BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
