"""The four-chip cell's whole run on four virtual CPU devices, one worker
per device over the engine mesh, under the cell's own limits: clean, it
is correct; with the timed path broken underneath, it is not, for each
fault the cell can have: the exchange between workers left out (each
worker's mean is itself), a round that returns its state unchanged, and
half of each worker's batch left out (its one row's first half of
positions, the mean taken over the rest).  Runs in a child process, since
the device count is fixed when JAX starts."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

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
elif fault == "unchanged":
    build = cell.build

    def broken(cfg, t, devs):
        bundle, vrl = build(cfg, t, devs)
        real_step = bundle.round_step
        return bundle._replace(round_step=lambda state, toks, labels: (
            state, real_step(state, toks, labels)[1])), vrl
    cell.build = broken
elif fault == "half_batch":
    from repro.train import train_loop
    ce = train_loop.cross_entropy_lm
    train_loop.cross_entropy_lm = lambda logits, labels: ce(
        logits[..., :logits.shape[-2] // 2, :],
        labels[..., :labels.shape[-1] // 2])
spec = files.benchmark()
c = files.cell(spec, "qwen2-0.5b.w4.s512.k2")
out = cell.run(spec, c, 3_000_000_031, 0.5, False,
               t0=time.perf_counter(), devices=jax.devices(),
               cfg=small.config(), traffic=small.traffic(4, batch=1),
               log=lambda s: None)
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


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_four_workers_broken_round_is_not_correct(fault):
    out = _child(fault)
    assert out["device"]["count"] == 4
    assert not out["correct"], out["checks"]
