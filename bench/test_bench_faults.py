"""A whole run of a cell on the CPU, past the harness's look for a chip,
with the timed path broken underneath: ``correct`` has to come out false.
A clean run of the same cell comes out true."""
import time

import jax
import pytest

from benchlib import cell, files, small

CELL = {"name": "qwen2-0.5b.s2048.k8", "config": "qwen2-0.5b",
        "traffic": "b2.s2048.k8", "chips": 1}
SEED = 3_000_000_021


def _run(monkeypatch, fault=None):
    if fault == "unchanged":
        build = cell.build

        def broken(cfg, t, devs):
            bundle, vrl = build(cfg, t, devs)
            real = bundle.round_step

            def round_step(state, toks, labels):
                return state, real(state, toks, labels)[1]
            return bundle._replace(round_step=round_step), vrl
        monkeypatch.setattr(cell, "build", broken)
    elif fault == "half_batch":
        from repro.train import train_loop
        ce = train_loop.cross_entropy_lm

        def half(logits, labels):
            h = logits.shape[0] // 2
            return ce(logits[:h], labels[:h])
        monkeypatch.setattr(train_loop, "cross_entropy_lm", half)
    spec = files.benchmark()
    return cell.run(spec, CELL, SEED, 0.5, False, t0=time.perf_counter(),
                    devices=jax.devices(), cfg=small.config(),
                    traffic=small.traffic(follow=1), log=lambda s: None)


def test_clean_run_is_correct(monkeypatch):
    out = _run(monkeypatch)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                   "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_round_is_not_correct(monkeypatch, fault):
    out = _run(monkeypatch, fault)
    assert not out["correct"], out["checks"]
