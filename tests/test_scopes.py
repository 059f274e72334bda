"""The round program carries the named scopes that a profile is split by.

The benchmark's trace reduction (``bench/benchlib/scopes.py``) attributes
device time by the HLO ``op_name`` of each operation, as ``obs.scopemap``
keeps it for the round ``core.engine.RoundCache`` compiled.  These tests run
the round of a small model of the qwen2-0.5b block, built as the benchmark
builds it (``update_backend="xla"`` on the CPU), through a ``RoundCache``
and read the recorded op names: one worker, and two workers over a
two-device mesh, each in a child process (the device count is fixed when
JAX starts).
"""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
TOP = ("model", "flat.flatten", "flat.unflatten", "engine.local_update",
       "engine.sync")
NESTED = ("attention", "head")

CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax, jax.numpy as jnp
from benchlib import cell, small, traffic as traffic_mod
from repro.core import engine
from repro.obs import scopemap

compiles = []      # backend compiles of the round (RoundCache's ``traced``)
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, fun_name="", **kw: compiles.append(name)
    if name.endswith("backend_compile_duration")
    and fun_name == "jit(traced)" else None)
w = int(sys.argv[3])
t = traffic_mod.check(dict(small.traffic(w), vrl={"update_backend": "xla"}))
bundle, _ = cell.build(small.config(), t, jax.devices()[:w])
state = bundle.init_state(jax.random.PRNGKey(0), w)
toks = jnp.zeros((t["k"], w, t["batch"], t["seq"]), jnp.int32)
rounds = engine.RoundCache(bundle.round_step)
counts = []
for _ in range(2):
    n = len(compiles)
    state, losses = rounds(state, toks, toks)
    losses.block_until_ready()
    counts.append(len(compiles) - n)
paths = scopemap.op_paths()
print(json.dumps({"names": sorted(set(paths.values())),
                  "all_reduces": scopemap.latest().as_text().count(
                      " all-reduce("),
                  "compiles": counts, "traces": rounds.compiles}))
"""

def _round_op_names(workers: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{max(workers, 1)}")
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT / "bench"),
                        str(ROOT / "src"), str(workers)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _components(path: str) -> list:
    """Path components with transform wrappers (``jvp(...)``, ...)
    peeled, as the trace reduction reads them."""
    out = []
    for c in path.split("/"):
        m = re.match(r"^[A-Za-z_][\w.]*\((.*)\)$", c)
        while m:
            c = m.group(1)
            m = re.match(r"^[A-Za-z_][\w.]*\((.*)\)$", c)
        out.append(c)
    return out


@pytest.fixture(scope="module", params=[1, 2], ids=["W1", "W2_mesh"])
def compiled(request):
    return request.param, _round_op_names(request.param)


def test_every_scope_is_in_the_round(compiled):
    w, got = compiled
    found = {c for n in got["names"] for p in n.split(";")
             for c in _components(p)}
    missing = [s for s in TOP + NESTED if s not in found]
    assert not missing, (w, missing)


def test_backward_carries_the_model_scope(compiled):
    _, got = compiled
    assert any("transpose(jvp(model))" in n for n in got["names"])
    assert any("jvp(model)" in n and "transpose" not in n
               for n in got["names"])


def test_top_level_scopes_are_disjoint(compiled):
    _, got = compiled
    for n in got["names"]:
        for p in n.split(";"):      # XLA joins merged ops' paths with ';'
            held = [s for s in TOP if s in _components(p)]
            assert len(held) <= 1, p


def test_attention_and_head_sit_inside_model(compiled):
    _, got = compiled
    paths = [_components(p) for n in got["names"] for p in n.split(";")]
    assert all("model" in c for c in paths if "head" in c)
    # attention's products run under the model, forward and backward
    assert any("model" in c and "attention" in c and "dot_general" in c
               for c in paths)


def test_sync_holds_the_mesh_all_reduce(compiled):
    w, got = compiled
    if w == 1:
        assert got["all_reduces"] == 0
        return
    # the one sync all-reduce of the round's parameters is in engine.sync
    assert any("engine.sync" in n and n.rsplit("/", 1)[-1] == "psum"
               for n in got["names"]), [n for n in got["names"]
                                        if "psum" in n]


def test_round_cache_compiles_once_and_records_the_round(compiled):
    _, got = compiled
    # the executable compiled ahead of the first call is the one the call
    # runs: one compile and one trace of the round, none on the second
    assert got["compiles"] == [1, 0]
    assert got["traces"] == 1
    assert got["names"]
