#!/usr/bin/env python3
"""Readings that the limits of a cell are set from, at the cell's own
size, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 101-112 \\
        --control-seeds 101-103 [--out DIR]

For each of ``--seeds``: the program's set-up and first rounds exactly as
a benchmark run drives them (``benchlib.cell.follow``), then the float32
reference over the same rounds, and the numbers the run compares.  The
largest of each over the seeds is its lower reading.

For each of ``--control-seeds``: the reference computed in bfloat16 (the
control), and the reference with a fault planted (half of each batch
left out, the mean taken over the rest; with W > 1 the exchange left
out), each compared with the float32 reference as the program would be.
The smallest of each is an upper reading.  A state left unchanged reads
1 on the update numbers and needs no run.

Prints one JSON object with every reading as its last line.  Builds the
program once and reuses it for every seed; the benchmark's own runs never
call this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    import gc

    import jax.numpy as jnp
    from benchlib import cell as cell_mod, files, traffic as traffic_mod
    from run import enable_cache, require_tpu

    spec = files.benchmark()
    cell = files.cell(spec, args.workload)
    devs = require_tpu(cell["chips"])[:cell["chips"]]
    enable_cache()
    cfg, t = files.config(cell), traffic_mod.check(files.traffic(cell))
    p = cell_mod.Program(cfg, t, devs)
    ref, m = p.ref, p.m
    f32 = p.reference()
    out = {"workload": args.workload, "program": {}, "control": {},
           "half_batch": {}, "no_exchange": {}}
    ref32 = {}
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        pool = traffic_mod.token_pool(t, m.vocab, seed)
        rounds = [pool[i] for i in range(t["follow"])]
        if seed in args.seeds:
            def feed(r):
                toks = jnp.asarray(pool[r])
                return toks, jnp.roll(toks, -1, axis=-1)
            c0 = time.perf_counter()
            state, prog, losses, _ = p.follow(feed, seed)
            del state, losses
            gc.collect()
            c1 = time.perf_counter()
        rd = f32.run(ref.init_params(m, seed), rounds)
        ref32[seed] = rd
        if seed in args.seeds:
            nums = p.compare(prog, rd, t)
            out["program"][seed] = nums
            print(f"calibrate: seed {seed} program {nums} (program "
                  f"{c1 - c0:.1f} s, reference "
                  f"{time.perf_counter() - c1:.1f} s)", flush=True)
    variants = {"control": dict(dtype=jnp.bfloat16),
                "half_batch": dict(half_batch=True)}
    if t["workers"] > 1:
        variants["no_exchange"] = dict(no_exchange=True)
    for name, kw in variants.items():
        r = p.reference(**kw)
        for seed in args.control_seeds:
            pool = traffic_mod.token_pool(t, m.vocab, seed)
            rounds = [pool[i] for i in range(t["follow"])]
            rd = r.run(ref.init_params(m, seed), rounds)
            nums = p.compare(cell_mod.as_program(rd), ref32[seed], t)
            out[name][seed] = nums
            print(f"calibrate: seed {seed} {name} {nums}", flush=True)
    out["device"] = {"kind": devs[0].device_kind, "chips": len(devs)}
    out["seconds"] = time.perf_counter() - t_start
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        with open(Path(args.out) / f"{args.workload}.json", "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
