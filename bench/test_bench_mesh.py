"""A four-worker cell's whole run on four virtual CPU devices, one
worker per device over the engine mesh: clean, it is correct; with the
exchange between workers left out (each worker's mean is itself), it is
not.  Runs in a child process, since the device count is fixed when JAX
starts.  The limits are those a four-chip qwen2-0.5b cell at 512 tokens
read on the chip with the rotary base at 1e4; a cell that the benchmark
runs takes its own from ``limits/``."""
import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent

CHILD = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
from benchlib import cell, files, small
fault = sys.argv[3]
if fault == "no_exchange":
    # every psum returns its own operand times the axis size: each
    # worker's "mean" over the workers is its own parameters
    real = jax.lax.psum
    jax.lax.psum = lambda x, axis_name, **kw: jax.tree.map(
        lambda a: a * real(1, axis_name), x)
c = {"name": "qwen2-0.5b.w4.s512.k2", "config": "qwen2-0.5b",
     "traffic": "w4.b1.s512.k2", "chips": 4}
limits = {k: {"limit": v} for k, v in dict(
    loss=3.3e-4, update=0.041, update2=0.044, delta=0.056, delta_sum=0.016,
    drift=0, nonfinite_rounds=0, window_compiles=0).items()}
out = cell.run(files.benchmark(), c, 3_000_000_031, 0.5, False,
               t0=time.perf_counter(), devices=jax.devices(),
               cfg=small.config(), traffic=small.traffic(4, batch=1),
               limits=limits, log=lambda s: None)
print(json.dumps(out))
"""


def _child(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = str(BENCH.parent / "src")
    p = subprocess.run([sys.executable, "-c", CHILD, str(BENCH), src, fault],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_workers_clean_and_without_exchange():
    clean = _child("none")
    assert clean["correct"], clean["checks"]
    assert clean["device"]["count"] == 4
    assert clean["checks"]["drift"]["value"] == 0.0
    broken = _child("no_exchange")
    assert not broken["correct"], broken["checks"]
    assert broken["checks"]["delta"]["value"] > broken["checks"]["delta"][
        "limit"]
