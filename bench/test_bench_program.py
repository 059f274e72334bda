"""What a traffic file sets in the program, the reference it names, and
the state that set-up builds in one call."""
import jax
import numpy as np
import pytest

from benchlib import cell, files, small

SEED = 2**32 + 7


def test_default_settings_are_the_train_py_round():
    from repro.configs.base import EngineConfig, VRLConfig
    t = small.traffic()
    assert cell.vrl_config(t) == VRLConfig(
        algorithm="vrl_sgd", comm_period=t["k"], learning_rate=t["lr"],
        inner_optimizer="sgd", warmup=False, update_backend="auto",
        engine=EngineConfig(block=0, round_scan=True, shards=1))


def test_vrl_block_reaches_the_program_settings():
    from repro.comm.compressors import CompressorSpec
    vrl = cell.vrl_config(small.traffic(vrl=dict(
        overlap=True, compress="int8", inner_optimizer="adam",
        moment_dtype="bfloat16", engine={"shards": 2})))
    assert vrl.overlap and vrl.inner_optimizer == "adam"
    assert vrl.moment_dtype == "bfloat16"
    assert isinstance(vrl.compress, CompressorSpec)
    assert vrl.compress.name == "int8"
    assert (vrl.engine.shards, vrl.engine.block) == (2, 0)
    hier = cell.vrl_config(small.traffic(4, vrl=dict(
        algorithm="hier_vrl_sgd", hier={"pods": 2, "k1": 2, "k2": 8}))).hier
    assert (hier.grid, hier.k1, hier.k2) == ((2, 2), 2, 8)


@pytest.mark.parametrize("vrl", [{"algorithm": "local_sgd"},
                                 {"inner_optimizer": "momentum",
                                  "momentum": 0.9},
                                 {"compress": "int8"}])
def test_reference_refuses_settings_it_does_not_follow(vrl):
    with pytest.raises(files.BenchError, match="cannot follow"):
        cell.Program(small.config(), small.traffic(vrl=vrl),
                     jax.devices()[:1])


def test_traffic_names_its_reference():
    cfg = small.config()
    assert files.reference(cfg, {}).__name__ == "bench_ref_dense"
    assert files.reference(cfg, {"reference": "dense"}).FOLLOWS
    with pytest.raises(files.BenchError, match="missing"):
        files.reference(cfg, {"reference": "no_such_reference"})


def test_state_from_one_call_is_the_program_init():
    """The state set-up makes in one jitted call from the seed is, to the
    bit, what the program's own init makes from the same weights."""
    p = cell.Program(small.config(), small.traffic(), jax.devices()[:1])
    got = p.make_state(p.ref.seed_key(SEED))
    want = p.bundle.engine.init(p.ref.init_params(p.m, SEED), 1)
    g, w = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(g) == len(w) > 0
    for a, b in zip(g, w):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
