"""The plain reference against the program's round at a small size on
the CPU, and the control (the reference in bfloat16) against the limits
of the cells."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import cell, files, small, traffic as traffic_mod

SEED = 2**33 + 11


def _program_and_reference(t):
    p = cell.Program(small.config(), t, jax.devices()[:1])
    pool = traffic_mod.token_pool(t, p.m.vocab, SEED)

    def feed(r):
        toks = jnp.asarray(pool[r])
        return toks, jnp.roll(toks, -1, axis=-1)

    _, prog, _, _ = p.follow(feed, SEED)
    rounds = [pool[i] for i in range(t["follow"])]
    return prog, p.reference, p.ref.init_params(p.m, SEED), rounds


@pytest.mark.parametrize("batch,k", [(2, 2), (1, 3)])
def test_reference_follows_the_program_round(batch, k):
    """Float32 on the CPU: the program's losses, first-round update and
    second-round change agree with the reference to rounding."""
    t = small.traffic(batch=batch, k=k)
    prog, mk, p0, rounds = _program_and_reference(t)
    nums = cell.compare(prog, mk().run(p0, rounds), t)
    assert set(nums) == {"loss", "update", "update2"}
    assert all(v < 1e-5 for v in nums.values()), nums


@pytest.mark.parametrize("workers,name", [(1, "qwen2-0.5b.s2048.k8"),
                                          (4, "qwen2-0.5b.w4.s512.k2")])
def test_control_fails_the_comparison(workers, name):
    """The reference computed in bfloat16 fails at least one of the
    numbers a cell of one or of four workers compares, at that cell's
    limits."""
    t = small.traffic(workers, batch=2 if workers == 1 else 1)
    cfg = small.config()
    ref = files.reference(cfg, t)
    m = ref.dims(cfg)
    mk = functools.partial(ref.Reference, m, cell.vrl_config(t),
                           workers=workers, devices=jax.devices()[:1])
    pool = traffic_mod.token_pool(t, m.vocab, SEED)
    rounds = [pool[i] for i in range(t["follow"])]
    p0 = ref.init_params(m, SEED)
    ref32 = mk().run(p0, rounds)
    ctrl = mk(dtype=jnp.bfloat16).run(p0, rounds)
    nums = cell.compare(cell.as_program(ctrl), ref32, t)
    lim = files.limits({"name": name})
    assert any(nums[k] > lim[k]["limit"] for k in nums if k in lim), (
        nums, lim)
    assert np.all(np.isfinite(ctrl.losses))


def test_half_batch_reading_differs():
    """A planted fault in the reference, half of each batch left out,
    moves the update reading far above rounding."""
    t = small.traffic()
    _, mk, p0, rounds = _program_and_reference(t)
    ref32 = mk().run(p0, rounds)
    half = mk(half_batch=True).run(p0, rounds)
    nums = cell.compare(cell.as_program(half), ref32, t)
    assert nums["update"] > 1e-2, nums
