"""The yardstick's counts against hand counts, and the benchmark's files
against each other."""
import dataclasses
import json
import re

import pytest

from benchlib import counts, files


def _cfg(name):
    return files.load_json(files.BENCH / "configs" / f"{name}.json")


# hand counts: embedding V*d; per layer q d*H*hd, k and v d*KV*hd, o H*hd*d,
# SwiGLU 3*d*ff, QKV biases (H + 2 KV)*hd, two norms 2*d; final norm d
QWEN_LAYER = (896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
              + (14 + 4) * 64 + 2 * 896)
GRANITE_LAYER = (2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048
                 + 3 * 2048 * 8192 + 2 * 2048)


@pytest.mark.parametrize("name,params,matmul,flops", [
    ("qwen2-0.5b", 494_032_768, 24 * (QWEN_LAYER - 18 * 64 - 2 * 896)
     + 151_936 * 896, 3_228_008_448),
    ("granite-3-2b", 587_243_520, 8 * (GRANITE_LAYER - 2 * 2048)
     + 49_155 * 2048, 3_724_578_816),
])
def test_counts_match_hand_counts(name, params, matmul, flops):
    cfg = _cfg(name)
    assert counts.param_count(cfg) == params
    assert counts.matmul_params(cfg) == matmul
    # 6 per matmul weight + 6 * L * S * H * hd of causal attention
    attn = 6 * cfg["num_hidden_layers"] * 2048 * cfg[
        "num_attention_heads"] * 64
    assert counts.train_flops_per_token(cfg, 2048) == 6 * matmul + attn
    assert counts.train_flops_per_token(cfg, 2048) == flops
    assert files.reference(cfg, {}).param_count(
        files.reference(cfg, {}).dims(cfg)) == params


def test_hand_counts_of_the_layers():
    assert QWEN_LAYER == 14_912_384
    assert 24 * QWEN_LAYER + 151_936 * 896 + 896 == 494_032_768
    assert GRANITE_LAYER == 60_821_504
    assert 8 * GRANITE_LAYER + 49_155 * 2048 + 2048 == 587_243_520


@pytest.mark.parametrize("name", ["qwen2-0.5b", "granite-3-2b"])
def test_config_views_agree_with_the_registry(name):
    """The published-name keys the reference reads and the program block
    the harness builds describe one model, and the program block is the
    registry's entry cut to the file's depth, with the published rotary
    base and norm epsilon where the registry keeps the defaults."""
    from repro.configs import registry
    cfg = _cfg(name)
    p = cfg["program"]
    assert p["d_model"] == cfg["hidden_size"]
    assert p["d_ff"] == cfg["intermediate_size"]
    assert p["num_layers"] == cfg["num_hidden_layers"]
    assert p["num_heads"] == cfg["num_attention_heads"]
    assert p["num_kv_heads"] == cfg["num_key_value_heads"]
    assert p["head_dim"] == cfg["head_dim"]
    assert p["vocab_size"] == cfg["vocab_size"]
    assert p["qkv_bias"] == cfg["attention_bias"]
    assert p["norm_eps"] == cfg["rms_norm_eps"]
    assert p["rope_theta"] == cfg["rope_theta"]
    from repro.configs.base import ModelConfig
    want = dataclasses.replace(registry.get_arch(name),
                               num_layers=cfg["num_hidden_layers"],
                               rope_theta=cfg["rope_theta"],
                               norm_eps=cfg["rms_norm_eps"])
    assert ModelConfig(**p) == want
    for key, cut in cfg["reduced"].items():
        assert cfg[key] == cut["run"]


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_files_line_up():
    """Every cell finds its configuration, traffic and limits, every
    per-layer metric its reader, and every name keeps to the benchmark's
    alphabet."""
    spec = files.benchmark()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    cfg_names = {c["name"] for c in spec["configs"]}
    for c in spec["configs"]:
        cfg = files.load_json(files.REPO / c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    metrics = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    for c in spec["workloads"]:
        assert c["config"] in cfg_names and c["chips"] in (1, 4)
        files.traffic(c)
        lim = files.limits(c)
        assert all(v["limit"] >= 0 for v in lim.values())
        for m in spec["per_layer"]:
            assert set(m.get("workloads", [])) <= {
                w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert callable(files.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
    for n in list(metrics) + list(cfg_names) + [
            w["name"] for w in spec["workloads"]] + [
            w["traffic"] for w in spec["workloads"]]:
        assert NAME.match(n), n
    assert len(json.dumps(spec)) < 64 * 1024


def _cli(cwd, env_extra=None):
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.s512.k2",
         "--seed", "3000000041", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_run_refuses_without_a_tpu():
    """Off the TPU the command exits non-zero and prints no result."""
    p = _cli(files.REPO)
    assert p.returncode == 1, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_refuses_with_the_benchmark_alone(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    cannot run it."""
    import shutil
    shutil.copy(files.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(files.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


class TwoKinds:
    """A toy reference of a hybrid with two layer kinds: in each period of
    ``period`` layers, one attention layer as the dense block has it and
    ``period - 1`` gated mixers (in_proj d -> 2e, out_proj e -> d, and a
    linear scan of 6 * e * state FLOP a token in training), each layer
    followed by a SwiGLU MLP; a tied head."""

    @staticmethod
    def dims(cfg):
        return cfg

    @staticmethod
    def _layers(m):
        attn = m["num_hidden_layers"] // m["period"]
        return attn, m["num_hidden_layers"] - attn

    @staticmethod
    def matmul_params(m):
        d, hd = m["hidden_size"], m["head_dim"]
        attn, mix = TwoKinds._layers(m)
        qkvo = (2 * m["num_attention_heads"]
                + 2 * m["num_key_value_heads"]) * d * hd
        return (attn * qkvo + mix * 3 * d * m["expand"]
                + m["num_hidden_layers"] * 3 * d * m["intermediate_size"]
                + m["vocab_size"] * d)

    @staticmethod
    def param_count(m):
        d = m["hidden_size"]
        return (TwoKinds.matmul_params(m) + 2 * m["num_hidden_layers"] * d
                + d)

    @staticmethod
    def train_flops_per_token(m, seq):
        attn, mix = TwoKinds._layers(m)
        return (6 * TwoKinds.matmul_params(m)
                + 6 * attn * seq * m["num_attention_heads"] * m["head_dim"]
                + 6 * mix * m["expand"] * m["state"])


def _two_kinds(monkeypatch):
    """The small configuration, named to the toy reference, which
    ``files.reference`` hands out for it."""
    from benchlib import small
    cfg = dict(small.config(), reference="two_kinds", num_hidden_layers=8,
               period=4, expand=128, state=16)
    real = files.reference
    monkeypatch.setattr(files, "reference", lambda c, traffic=None: (
        TwoKinds if c["reference"] == "two_kinds" else real(c, traffic)))
    return cfg


# by hand, for d 64, 4/2 heads of 16, ff 128, vocab 512, 8 layers in two
# periods of 1 attention + 3 mixers (e 128, state 16):
#   attention q, k, v, o: (4 + 4 + 2 + 2) * 16 * 64 = 12,288, x 2 layers
#   mixers: 3 * 64 * 128 = 24,576, x 6; MLPs 3 * 64 * 128, x 8; head
#   512 * 64 = 32,768: matmul weights 24,576 + 147,456 + 196,608 + 32,768
TWO_KINDS_MATMUL = 401_408
TWO_KINDS_PARAMS = TWO_KINDS_MATMUL + 2 * 8 * 64 + 64   # norms
TWO_KINDS_FLOPS_512 = (6 * TWO_KINDS_MATMUL + 6 * 2 * 512 * 4 * 16
                       + 6 * 6 * 128 * 16)


def test_counts_come_from_the_configurations_reference(monkeypatch):
    """A configuration whose reference has two layer kinds is counted by
    that reference: in ``counts``, in the traced context that
    ``round.mfu`` reads, and in ``engine.update_roofline``'s bytes, not
    by the dense formula."""
    import jax
    import jax.numpy as jnp

    from benchlib import cell, scopes
    cfg = _two_kinds(monkeypatch)
    assert counts.matmul_params(cfg) == TWO_KINDS_MATMUL
    assert counts.param_count(cfg) == TWO_KINDS_PARAMS
    assert counts.train_flops_per_token(cfg, 512) == TWO_KINDS_FLOPS_512
    dense = files.reference(dict(cfg, reference="dense"))
    m = dense.dims(cfg)
    assert dense.param_count(m) != TWO_KINDS_PARAMS
    assert dense.train_flops_per_token(m, 512) != TWO_KINDS_FLOPS_512

    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(files, "peaks", lambda kind: peaks)
    t = {"workers": 4, "seq": 512, "k": 2}
    step = jax.jit(lambda s, toks, labels: (s + 1, jnp.sum(toks)))
    ctx = cell.traced_segment(
        jnp.zeros(()), step, lambda r: (jnp.full((4,), r), None), 0, t,
        cfg, jax.devices()[:1], 1.0, lambda s: None)
    assert ctx["flops_per_token"] == TWO_KINDS_FLOPS_512
    ctx.update(tokens_per_s=10_000.0, chips=4)
    assert files.metric_reader("round.mfu")(ctx) == pytest.approx(
        100 * 10_000.0 * TWO_KINDS_FLOPS_512 / (4 * 197e12))
    monkeypatch.setattr(scopes, "ms_per_round", lambda ctx, scope: 2.0)
    # k x W / chips x parameters x 16 B over 2 ms at 819 GB/s
    assert files.metric_reader("engine.update_roofline")(ctx) == \
        pytest.approx(100 * 2 * 1 * TWO_KINDS_PARAMS * 16 / (2e-3 * 819e9))


@pytest.mark.parametrize("count,args", [("param_count", ()),
                                        ("train_flops_per_token", (2048,))])
def test_a_reference_without_counts_is_an_error(monkeypatch, count, args):
    import types
    bare = types.ModuleType("bench_ref_bare")
    bare.dims = lambda cfg: cfg
    monkeypatch.setattr(files, "reference", lambda c, traffic=None: bare)
    with pytest.raises(files.BenchError, match="bench_ref_bare.*" + count):
        getattr(counts, count)(_cfg("qwen2-0.5b"), *args)
