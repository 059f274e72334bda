"""Elastic fault tolerance: schedules, membership invariants, guards.

Four layers, matching the fault subsystem's own layering:

1. ``repro.fault.FaultSchedule`` — the spec grammar round-trips, random
   schedules are seed-deterministic, gradient poisons land at the right
   (step, worker) slot and fire exactly ONCE (rollback replays are
   clean), while crash/rejoin masks are a pure fold (replays see the
   same membership).
2. Engine membership (``VRLConfig.membership``) — with the mask fully
   active the trajectory is BITWISE identical to the membership=False
   engine (flat at a non-power-of-2 W, where sum*(1/n) vs sum/n rounding
   would differ, and hierarchical); every drop/rejoin repair restores
   Σ_i Δ_i = 0 (and Σ_i B_i = 0 for BVR) over the active set; a dead
   worker's NaNs never leak into survivors; the repair composes with
   compressed sync (EF residuals of dropped workers zeroed) and
   overlapped rounds; EASGD refuses membership loudly.
3. Train-loop hooks — ``StepBundle.round_step_fault`` with an all-ones
   multiplier reproduces ``round_step`` exactly; a NaN multiplier makes
   exactly the targeted worker sick and ``StepBundle.health`` flips;
   the reference backend refuses membership.
4. Driver flag validation — out-of-range flags and malformed/impossible
   fault specs exit early with named messages.

The collective-count acceptance (masked sync is still exactly ONE
all-reduce per round on an 8-device mesh, and full-mask mesh parity) runs
in a subprocess, same idiom as tests/test_engine_collectives.py.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import HierConfig, VRLConfig
from repro.core import flat_algorithms, make_engine
from repro.fault import FaultEvent, FaultSchedule

# ---------------------------------------------------------------- schedule


def test_spec_parse_roundtrip():
    fs = FaultSchedule.parse("nan@1:12, crash@1:30,rejoin@1:60,killsave:50")
    assert len(fs) == 4
    assert fs.describe() == "nan@1:12,crash@1:30,killsave:50,rejoin@1:60"
    assert fs.events[0] == FaultEvent("nan", 12, 1)
    assert fs.membership_events() == [FaultEvent("crash", 30, 1),
                                      FaultEvent("rejoin", 60, 1)]


def test_scale_spec_parse_roundtrip():
    fs = FaultSchedule.parse("scale@0:20:1e3,nan@1:12")
    assert fs.describe() == "nan@1:12,scale@0:20:1000"
    assert fs.events[1] == FaultEvent("scale", 20, 0, 1e3)
    # describe() output re-parses to the same schedule
    assert FaultSchedule.parse(fs.describe()).events == fs.events
    # fractional and sub-1 multipliers survive the g-format roundtrip
    fs2 = FaultSchedule.parse("scale@2:5:0.125")
    assert FaultSchedule.parse(fs2.describe()).events == fs2.events


@pytest.mark.parametrize("bad,msg", [
    ("frob@1:3", "unknown fault kind"),
    ("nan@1", "no ':step'"),
    ("nan:3", "needs a worker"),
    ("nan@1:x", "not an integer"),
    ("nan@z:3", "not an integer"),
    ("nan@-1:3", "worker must be >= 0"),
    ("nan@1:-3", "step must be >= 0"),
    ("killsave@2:3", "killsave takes no worker"),
    ("  ,  ", "contains no events"),
    ("scale@1:12", "needs a multiplier"),
    ("scale@1:12:zzz", "not a float"),
    ("scale@1:12:inf", "multiplier must be finite"),
    ("scale@1:12:nan", "multiplier must be finite"),
])
def test_spec_errors_are_named(bad, msg):
    with pytest.raises(ValueError, match=msg):
        FaultSchedule.parse(bad)


def test_random_schedule_is_seed_deterministic():
    a = FaultSchedule.random(100, 8, seed=7, n_grad=2, n_churn=2,
                             killsave=True)
    b = FaultSchedule.random(100, 8, seed=7, n_grad=2, n_churn=2,
                             killsave=True)
    assert a.describe() == b.describe()
    c = FaultSchedule.random(100, 8, seed=8, n_grad=2, n_churn=2,
                             killsave=True)
    assert a.describe() != c.describe()
    # every drawn mask keeps at least one survivor
    for t in range(100):
        assert a.active_at(t, 8).sum() >= 1
    with pytest.raises(ValueError, match=">= 2 workers"):
        FaultSchedule.random(100, 1, seed=0)


def test_grad_mul_placement_and_single_fire():
    fs = FaultSchedule.parse("nan@2:5,inf@0:6")
    assert fs.grad_mul(0, 4, 4) is None          # clean round -> None
    m = fs.grad_mul(4, 4, 4)                     # round covering [4, 8)
    assert m.shape == (4, 4)
    assert np.isnan(m[1, 2]) and np.isinf(m[2, 0])
    assert (m[np.isfinite(m)] == 1.0).all()
    # consumed: the rollback replay of the same round is clean
    assert fs.grad_mul(4, 4, 4) is None


def test_scale_grad_mul_is_finite_and_placed():
    fs = FaultSchedule.parse("scale@3:6:1e3")
    m = fs.grad_mul(4, 4, 4)                     # round covering [4, 8)
    assert m[2, 3] == 1e3
    assert np.isfinite(m).all()                  # silent: no NaN/Inf
    assert (m[m != 1e3] == 1.0).all()
    assert fs.grad_mul(4, 4, 4) is None          # one-shot


def test_membership_fold_is_pure():
    fs = FaultSchedule.parse("crash@1:3,rejoin@1:7,crash@2:5")
    np.testing.assert_array_equal(fs.active_at(2, 4), [1, 1, 1, 1])
    np.testing.assert_array_equal(fs.active_at(4, 4), [1, 0, 1, 1])
    np.testing.assert_array_equal(fs.active_at(6, 4), [1, 0, 0, 1])
    # replaying an earlier step after a rollback sees the same mask
    np.testing.assert_array_equal(fs.active_at(4, 4), [1, 0, 1, 1])
    np.testing.assert_array_equal(fs.active_at(9, 4), [1, 1, 0, 1])
    # killsave is one-shot across the whole run, grad faults likewise
    fs2 = FaultSchedule.parse("killsave:5")
    assert not fs2.killsave_at(4)
    assert fs2.killsave_at(6) and not fs2.killsave_at(7)


# ------------------------------------------------- engine membership layer

W = 5                # deliberately non-power-of-2: 1/W is not exact
TEMPLATE = {"w": jnp.zeros((12, 8)), "b": jnp.zeros((5,))}
P0 = {"w": jnp.ones((12, 8)) * 0.3, "b": jnp.ones((5,)) * -0.2}


def _cfg(alg="vrl_sgd", backend="xla", **kw):
    return VRLConfig(algorithm=alg, comm_period=4, learning_rate=0.05,
                     weight_decay=0.0, warmup=False,
                     update_backend=backend, **kw)


def _gk(eng, state, r, k=4, scale=0.1):
    return jax.tree.map(
        lambda x: jnp.stack([jnp.sin(x + r * k + i) * scale
                             for i in range(k)]),
        eng.params_tree(state))


def _run(cfg, rounds=3, w=W):
    eng = make_engine(cfg, TEMPLATE)
    state = eng.init(P0, w)
    rs = jax.jit(eng.round_step, donate_argnums=(0,))
    for r in range(rounds):
        state = rs(state, _gk(eng, state, r))
    return eng, state


@pytest.mark.parametrize("alg",
                         [a for a in flat_algorithms() if a != "easgd"])
def test_full_mask_is_bitwise_identical(alg):
    """The fault-free path costs nothing: with every worker active the
    membership engine's trajectory equals the membership=False engine
    BITWISE, at W=5 where a masked mean computed as sum/n (instead of
    the baseline's algebraically-simplified sum*(1/n)) would diverge in
    the last bit."""
    _, s0 = _run(_cfg(alg))
    _, s1 = _run(_cfg(alg, membership=True))
    assert np.array_equal(np.asarray(s0.params), np.asarray(s1.params))
    if hasattr(s0, "delta") and not isinstance(s0.delta, tuple):
        assert np.array_equal(np.asarray(s0.delta), np.asarray(s1.delta))


def test_easgd_refuses_membership():
    with pytest.raises(ValueError, match="easgd"):
        make_engine(_cfg("easgd", membership=True), TEMPLATE)


def test_drop_repairs_invariant_and_contains_nan():
    """Dropping a worker recentres Δ over the survivors (Σ Δ = 0 again),
    and a dead worker's NaN rows never reach an active row or the
    average model — the sync masks with where, not multiply."""
    eng, s = _run(_cfg(membership=True), rounds=2)
    setm = jax.jit(eng.set_membership, donate_argnums=(0,))
    mask = np.array([1, 0, 1, 1, 1], np.float32)
    s = setm(s, mask)
    act = np.asarray(s.member.active).reshape(-1) > 0
    np.testing.assert_array_equal(act, mask > 0)
    assert float(s.member.n_active) == 4.0
    d = np.asarray(s.delta)
    assert np.abs(d[act].sum(0)).max() < 1e-5
    assert np.abs(d[~act]).max() == 0.0          # dropped rows zeroed
    # poison the dead row, run two rounds: survivors stay finite
    pm = np.array(s.params)
    pm[1] = np.nan
    s = s._replace(params=jnp.asarray(pm))
    rs = jax.jit(eng.round_step, donate_argnums=(0,))
    for r in range(2):
        s = rs(s, _gk(eng, s, r))
    assert np.isfinite(np.asarray(s.params)[act]).all()
    for leaf in jax.tree.leaves(eng.average_model(s)):
        assert np.isfinite(np.asarray(leaf)).all()
    # rejoin: the sick worker restarts from the continuing consensus
    s = setm(s, np.ones(W, np.float32))
    assert float(s.member.n_active) == float(W)
    assert np.isfinite(np.asarray(s.params)).all()
    assert np.abs(np.asarray(s.delta).sum(0)).max() < 1e-5
    xhat = np.asarray(s.params)[0]
    np.testing.assert_array_equal(np.asarray(s.params)[1], xhat)


def test_bvr_bias_invariant_survives_drop():
    eng, s = _run(_cfg("bvr_l_sgd", membership=True), rounds=2)
    s = jax.jit(eng.set_membership)(s, np.array([1, 1, 0, 1, 1],
                                                np.float32))
    act = np.asarray(s.member.active).reshape(-1) > 0
    assert np.abs(np.asarray(s.delta)[act].sum(0)).max() < 1e-5
    assert np.abs(np.asarray(s.bias)[act].sum(0)).max() < 1e-5


def test_membership_composes_with_compression():
    """A dropped worker's error-feedback residual is zeroed (its backlog
    has no owner) and the compressed masked sync keeps survivors
    finite."""
    from repro.comm import compressors as cc

    cfg = _cfg(membership=True, compress=cc.parse_compressor("int8"))
    eng, s = _run(cfg, rounds=2)
    s = jax.jit(eng.set_membership)(s, np.array([0, 1, 1, 1, 1],
                                                np.float32))
    assert np.abs(np.asarray(s.comm.resid)[0]).max() == 0.0
    rs = jax.jit(eng.round_step, donate_argnums=(0,))
    for r in range(2):
        s = rs(s, _gk(eng, s, r))
    act = np.asarray(s.member.active).reshape(-1) > 0
    assert np.isfinite(np.asarray(s.params)[act]).all()


def test_membership_composes_with_overlap():
    """Overlapped rounds: the repair reseeds a dropped worker's pending
    contribution from the consensus, and post-drop rounds keep the
    invariant on the active set."""
    eng, s = _run(_cfg(membership=True, overlap=True), rounds=2)
    s = jax.jit(eng.set_membership)(s, np.array([1, 0, 1, 1, 1],
                                                np.float32))
    rs = jax.jit(eng.round_step, donate_argnums=(0,))
    for r in range(2):
        s = rs(s, _gk(eng, s, r))
    act = np.asarray(s.member.active).reshape(-1) > 0
    assert np.isfinite(np.asarray(s.params)[act]).all()
    assert np.abs(np.asarray(s.delta)[act].sum(0)).max() < 1e-3


def test_hier_membership_pod_and_worker_drop():
    """Hierarchical: dropping a worker preserves the intra-pod invariant
    (Σ Δ1 = 0 over the pod's survivors); dropping a WHOLE pod preserves
    the cross-pod invariant (Σ Δ2 = 0 over alive pods, n_active counts
    pods); rejoining everyone restores both.  Full-mask trajectory is
    bitwise the membership=False hierarchical engine."""
    grid = (2, 3)
    cfgh = VRLConfig(algorithm="hier_vrl_sgd", learning_rate=0.05,
                     update_backend="xla", membership=True,
                     hier=HierConfig(k1=2, k2=4, grid=grid))

    def runh(cfg):
        e = make_engine(cfg, TEMPLATE)
        s = e.init(P0, 6)
        rs = jax.jit(e.round_step, donate_argnums=(0,))
        for r in range(3):
            s = rs(s, _gk(e, s, r, k=2))
        return e, s

    _, s0 = runh(dataclasses.replace(cfgh, membership=False))
    engh, sh = runh(cfgh)
    assert np.array_equal(np.asarray(s0.params), np.asarray(sh.params))

    seth = jax.jit(engh.set_membership)
    m = np.ones(grid, np.float32)
    m[0, 1] = 0          # one worker out of pod 0
    m[1, :] = 0          # all of pod 1
    sh = seth(sh, m)
    assert float(sh.member.n_active) == 1.0      # alive PODS
    np.testing.assert_array_equal(
        np.asarray(sh.member.n_pod).reshape(-1), [2.0, 0.0])
    keep = np.asarray(sh.member.active)[..., 0, 0] > 0
    d1 = np.asarray(sh.delta1)
    assert np.abs((d1[0] * keep[0][:, None, None]).sum(0)).max() < 1e-5
    d2 = np.asarray(sh.delta2)
    alive = np.asarray(sh.member.n_pod).reshape(-1) > 0
    assert np.abs(d2[alive].sum(0)).max() < 1e-5
    rsh = jax.jit(engh.round_step, donate_argnums=(0,))
    for r in range(2):
        sh = rsh(sh, _gk(engh, sh, r, k=2))
    assert np.isfinite(np.asarray(sh.params)).all()
    sh = seth(sh, np.ones(grid, np.float32))
    assert float(sh.member.n_active) == 2.0
    assert np.abs(np.asarray(sh.delta2).sum(0)).max() < 1e-5


# ---------------------------------------------------- train-loop fault hooks


def _bundle(backend="auto", membership=True):
    from repro.configs import registry
    from repro.train.train_loop import make_train_step

    cfg = registry.smoke_arch("qwen2-0.5b", num_layers=2, d_model=64,
                              d_ff=128, vocab_size=64, num_heads=4,
                              num_kv_heads=2, head_dim=16)
    vrl = VRLConfig(algorithm="vrl_sgd", comm_period=3, learning_rate=0.2,
                    weight_decay=0.0, warmup=False,
                    update_backend=backend, membership=membership)
    return make_train_step(cfg, vrl, remat=False)


def test_round_step_fault_clean_matches_round_step():
    """An all-ones multiplier is a no-op: the fault round reproduces the
    clean round bitwise, so the chaos harness can't perturb a healthy
    run."""
    bundle = _bundle()
    w, b, sq, k = 2, 2, 16, 3
    toks = jax.random.randint(jax.random.PRNGKey(3), (k, w, b, sq), 0, 64)
    labels = jnp.roll(toks, -1, -1)
    s_a = bundle.init_state(jax.random.PRNGKey(0), w)
    s_b = bundle.init_state(jax.random.PRNGKey(0), w)
    s_a, l_a = jax.jit(bundle.round_step)(s_a, toks, labels)
    gmul = jnp.ones((k, w), jnp.float32)
    s_b, l_b = jax.jit(bundle.round_step_fault)(s_b, toks, labels, gmul)
    np.testing.assert_array_equal(np.asarray(l_a), np.asarray(l_b))
    assert np.array_equal(np.asarray(s_a.params), np.asarray(s_b.params))


def test_nan_poison_trips_health_and_prior_drop_contains():
    """A NaN multiplier on an ACTIVE worker poisons the round-closing
    sync (every worker averages it in) and health() goes False — the
    signal the divergence guard rolls back on.  The same poisoned round
    run AFTER dropping that worker stays healthy: the masked sync reads
    no dead rows, so the sick worker's NaNs never cross."""
    bundle = _bundle()
    w, b, sq, k = 2, 2, 16, 3
    toks = jax.random.randint(jax.random.PRNGKey(3), (k, w, b, sq), 0, 64)
    labels = jnp.roll(toks, -1, -1)
    health = jax.jit(bundle.health)
    rfault = jax.jit(bundle.round_step_fault)
    gmul = jnp.ones((k, w), jnp.float32).at[1, 1].set(jnp.nan)

    state = bundle.init_state(jax.random.PRNGKey(0), w)
    sick, losses = rfault(state, toks, labels, gmul)
    assert not bool(health(sick, losses[-1]))
    assert np.isnan(np.asarray(sick.params)).any()

    state = bundle.init_state(jax.random.PRNGKey(0), w)
    state = jax.jit(bundle.engine.set_membership)(
        state, np.array([1, 0], np.float32))
    state, losses = rfault(state, toks, labels, gmul)
    assert bool(health(state, losses[-1]))
    assert np.isfinite(np.asarray(state.params)[0]).all()


def test_reference_backend_refuses_membership():
    with pytest.raises(ValueError, match="membership"):
        _bundle(backend="reference")


# --------------------------------------------------- driver flag validation


@pytest.mark.parametrize("flags,msg", [
    (["--deadline", "1.5"], "probability in \\[0, 1\\]"),
    (["--ckpt-every", "0"], "--ckpt-every must be a positive"),
    (["--shards", "0"], "--shards must be >= 1"),
    (["--steps", "-3"], "--steps must be >= 1"),
    (["--k", "0"], "--k must be >= 1"),
    (["--workers", "0"], "--workers must be >= 1"),
    (["--ckpt-retain", "-1"], "--ckpt-retain must be >= 0"),
    (["--max-retries", "-1"], "--max-retries must be >= 0"),
    (["--workers", "2", "--faults", "nan@5:3"],
     "targets a worker >= --workers"),
    (["--workers", "2", "--faults", "crash@0:3,crash@1:4"],
     "no active worker at step 4"),
    (["--workers", "2", "--faults", "frob@0:3"], "unknown fault kind"),
    (["--membership", "--backend", "reference"],
     "membership"),
])
def test_bad_flags_exit_with_named_message(flags, msg):
    from repro.launch import train

    with pytest.raises(SystemExit, match=msg):
        train.main(["--smoke", "--steps", "4"] + flags)


def test_guard_catches_scale_poison_and_rolls_back(capsys):
    """A finite scale poison passes every finiteness check — the state
    never goes NaN — so ONLY the loss-trend guard can catch it.  Poison
    the first local step of a round: that round's mean loss blows up,
    the guard rolls back to the round snapshot, and the consumed fault
    lets the replay finish clean."""
    from repro.launch import train

    train.main(["--smoke", "--steps", "6", "--workers", "4",
                "--batch", "2", "--seq", "32", "--k", "2",
                "--lr", "0.05", "--guard", "--max-retries", "2",
                "--faults", "scale@1:2:1e4", "--log-every", "1"])
    out = capsys.readouterr().out
    assert "gradient fault in round [2, 4)" in out
    assert "loss blow-up" in out                 # the trend branch fired,
    assert "non-finite state" not in out         # not the finiteness one
    assert "rolled back to step 2 (retry 1/2)" in out
    assert "done: 6 steps" in out


# ------------------------------------- collective count on an 8-device mesh

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import json
    import re
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import VRLConfig
    from repro.core import make_engine

    mesh = jax.make_mesh((8,), ("data",), devices=jax.devices())
    template = {"w": jnp.zeros((64, 16)), "b": jnp.zeros((33,))}
    cfg = VRLConfig(algorithm="vrl_sgd", comm_period=4, learning_rate=0.05,
                    weight_decay=0.0, warmup=False, update_backend="xla",
                    membership=True)
    eng = make_engine(cfg, template, mesh=mesh, worker_axes=("data",))
    p0 = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 16)),
          "b": jax.random.normal(jax.random.PRNGKey(1), (33,))}

    def shard(x):
        nd = getattr(x, "ndim", 0)
        spec = P("data", None, None) if nd == 3 else P(*([None] * nd))
        return jax.device_put(x, NamedSharding(mesh, spec))

    state = jax.tree.map(shard, eng.init(p0, 8))

    def count_ar(hlo):
        return len(re.findall(r"all-reduce(?:-start)?\\(", hlo))

    out = {}
    # the acceptance property: the MASKED sync is still exactly one
    # all-reduce (n_active rides in state, no survivor-count collective),
    # and the whole compiled round keeps one collective per k steps
    hlo_sync = jax.jit(eng.sync).lower(state).compile().as_text()
    out["sync_all_reduce"] = count_ar(hlo_sync)
    gk = jax.tree.map(lambda x: jnp.stack([jnp.sin(3.0 * x + t) + 0.1 * x
                                           for t in range(4)]),
                      eng.params_tree(state))
    hlo_round = jax.jit(eng.round_step, donate_argnums=(0,)
                        ).lower(state, gk).compile().as_text()
    out["round_all_reduce"] = count_ar(hlo_round)
    # the repair itself is collective-frugal: one jit covers every mask
    hlo_m = jax.jit(eng.set_membership).lower(
        state, jnp.ones((8,), jnp.float32)).compile().as_text()
    out["repair_all_reduce"] = count_ar(hlo_m)

    # full-mask mesh parity: same trajectory as membership=False
    eng0 = make_engine(dataclasses.replace(cfg, membership=False),
                       template, mesh=mesh, worker_axes=("data",))
    s0 = jax.tree.map(shard, eng0.init(p0, 8))
    s1 = state
    r0 = jax.jit(eng0.round_step, donate_argnums=(0,))
    r1 = jax.jit(eng.round_step, donate_argnums=(0,))
    for r in range(3):
        gk = jax.tree.map(lambda x: jnp.stack(
            [jnp.sin(3.0 * x + r * 4 + t) + 0.1 * x for t in range(4)]),
            eng0.params_tree(s0))
        s0 = r0(s0, gk)
        s1 = r1(s1, gk)
    out["mesh_full_mask_bitwise"] = bool(np.array_equal(
        np.asarray(s0.params), np.asarray(s1.params)))

    # drop two workers ON the mesh: invariant holds under sharding
    s1 = jax.jit(eng.set_membership)(
        s1, jnp.array([1, 0, 1, 1, 0, 1, 1, 1], jnp.float32))
    act = np.asarray(s1.member.active).reshape(-1) > 0
    d = np.asarray(s1.delta)
    out["mesh_drop_sum_delta"] = float(np.abs(d[act].sum(0)).max())
    out["mesh_drop_n_active"] = float(np.asarray(s1.member.n_active))
    print(json.dumps(out))
""")


def test_masked_sync_is_still_one_all_reduce():
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    # membership must not add a collective: one all-reduce, total — the
    # masked mean's divisor comes from state, not a second reduction
    assert out["sync_all_reduce"] == 1, out
    assert out["round_all_reduce"] == 1, out
    # the out-of-round repair needs a bounded handful of collectives
    # (consensus + recenters), far from per-leaf
    assert out["repair_all_reduce"] <= 8, out
    assert out["mesh_full_mask_bitwise"] is True, out
    assert out["mesh_drop_sum_delta"] < 1e-5, out
    assert out["mesh_drop_n_active"] == 6.0, out
