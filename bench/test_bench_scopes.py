"""The scope reduction against hand counts on a recorded chip trace.

``testdata/scopes_s512.json`` is a slice of one traced round of
``qwen2-0.5b.s512.k2`` on a TPU v5e: ``trace`` in ``xtrace.load``'s plain
form (times in ns from the traced window's start) and ``paths``, the
round's {instruction: op_name} as the program's ``obs.scopemap`` gives it.
The slice holds the second local step's flatten and update, the round's
sync, then the next round's unflatten, forward and head, up to its
backward.  It holds one local update, so a reader sees it as one round
with k = 1."""
import json
from pathlib import Path

import pytest

from benchlib import files, scopes, xtrace

FIXTURE = Path(__file__).resolve().parent / "testdata" / "scopes_s512.json"

# hand counts from the fixture's events (ns)
LOCAL_SGD = 11_296_794.0            # custom-call vrl_local_sgd.12
SYNC = 14_700_452.0                 # custom-call vrl_sync.1
FLATTEN = 457.0 + 8_889_009.0 + 6_006_942.0   # concatenate, pad, reshape
MASK = 752.0        # iota_compare_fusion.4: attention's mask, hoisted out
                    # of the model's jvp, keeps ``attention`` only


@pytest.fixture(scope="module")
def fixture():
    d = json.loads(FIXTURE.read_text())
    return {"window": d["window"], "devices": d["devices"]}, d["paths"]


@pytest.fixture
def fx(fixture):
    return fixture[0]


@pytest.fixture
def paths(fixture, monkeypatch):
    """The fixture's paths, as the program's map gives them."""
    monkeypatch.setattr(scopes, "program_paths", lambda: fixture[1])
    return fixture[1]


def test_wrapper_peeling_and_top_scope():
    assert scopes.peel("vmap(transpose(jvp(model)))") == "model"
    assert scopes.peel("jit(traced)") == "traced"
    assert scopes.peel("flat.flatten") == "flat.flatten"
    bwd = ("jit(traced)/while/body/closed_call/vmap(transpose(jvp(model)))"
           "/while/body/closed_call/checkpoint/attention/dot_general")
    assert scopes.top_scope(bwd) == "model"
    assert "attention" in scopes.components(bwd)
    # XLA joins the paths of merged operations with ';': the first
    # top-level scope along the string takes the op
    merged = ("jit(traced)/while/body/closed_call/flat.flatten/reshape;"
              "vmap()/transpose")
    assert scopes.top_scope(merged) == "flat.flatten"
    assert scopes.top_scope("jit(traced)/while") is None
    assert scopes.top_scope("") is None
    assert scopes.top_scope("jit(traced)/engine.sync/vrl_sync/"
                            "pallas_call") == "engine.sync"


def test_instruction_of_a_trace_op():
    assert scopes.instruction("fusion:fusion.585:f32[2,14,2048,64]") == \
        "fusion.585"
    assert scopes.instruction("custom-call:vrl_sync.1:(f32[1], f32[1])") \
        == "vrl_sync.1"
    # a name xtrace could not parse has no instruction
    assert scopes.instruction("%weird text") == ""


def test_hand_counts(fx, paths):
    s = scopes.seconds(fx, paths)["0"]
    assert s["engine.local_update"] == pytest.approx(LOCAL_SGD * 1e-9)
    assert s["engine.sync"] == pytest.approx(SYNC * 1e-9)
    assert s["flat.flatten"] == pytest.approx(FLATTEN * 1e-9)


def test_scopes_and_unscoped_add_up_to_busy(fx, paths):
    s = scopes.seconds(fx, paths)["0"]
    top = sum(s[sc] for sc in scopes.TOP)
    assert top + s["unscoped"] == pytest.approx(s["busy"], rel=1e-12)
    # the same busy time as the idle share's
    assert s["busy"] == pytest.approx(xtrace.busy_s(fx)["0"], rel=1e-12)
    assert s["unscoped"] > 0


def test_attention_and_head_nest_inside_model(fx, paths):
    s = scopes.seconds(fx, paths)["0"]
    assert 0 < s["attention"] < s["model"]
    assert 0 < s["head"] < s["model"]
    model = scopes._intervals(fx, "0", paths,
                              lambda p: scopes.top_scope(p) == "model")
    for sc, outside in (("attention", MASK), ("head", 0.0)):
        iv = scopes._intervals(fx, "0", paths, lambda p, sc=sc:
                               sc in scopes.components(p))
        rest = xtrace._minus(iv, model)
        assert scopes._length(rest) == pytest.approx(outside * 1e-9), sc


def test_containers_count_as_busy_only(fx):
    # a lone container: busy, under no scope
    t = {"window": [0.0, 100.0],
         "devices": {"0": [["while:while.1:(s32[])", 0.0, 100.0],
                           ["fusion:f.1:f32[8]", 10.0, 20.0]]}}
    s = scopes.seconds(t, {"while.1": "jit(traced)/while",
                           "f.1": "jit(traced)/engine.sync/add"})["0"]
    assert s["busy"] == pytest.approx(100e-9)
    assert s["engine.sync"] == pytest.approx(20e-9)
    assert s["unscoped"] == pytest.approx(80e-9)


def _ctx(fx):
    cfg = files.load_json(files.BENCH / "configs" / "qwen2-0.5b.json")
    return {"trace": fx, "rounds_traced": 1, "k": 1, "workers": 1,
            "chips": 1, "config": cfg, "traffic": {"workers": 1},
            "peaks": files.peaks("TPU v5 lite")}


def test_readers_on_the_fixture(fx, paths):
    ctx = _ctx(fx)
    s = scopes.seconds(fx, paths)["0"]
    read = lambda name: files.metric_reader(name)(ctx)   # noqa: E731
    assert read("engine.local_update_ms") == pytest.approx(LOCAL_SGD * 1e-6)
    assert read("engine.sync_ms") == pytest.approx(SYNC * 1e-6)
    assert read("flat.flatten_ms") == pytest.approx(FLATTEN * 1e-6)
    for name, sc in (("model.fwd_bwd_ms", "model"),
                     ("model.attention_ms", "attention"),
                     ("model.head_ms", "head"),
                     ("flat.unflatten_ms", "flat.unflatten")):
        assert read(name) == pytest.approx(1e3 * s[sc]), name
    # one step reads p, g, Δ and writes p: 16 B per parameter
    need = 494_032_768 * 16
    assert read("engine.update_roofline") == pytest.approx(
        100 * need / (LOCAL_SGD * 1e-9 * 819e9))
    assert 80 < read("engine.update_roofline") < 90
    assert read("device.unscoped_share") == pytest.approx(
        100 * s["unscoped"] / s["busy"])


def test_readers_of_a_program_without_scopes(fx, monkeypatch):
    # a program that keeps no map (None), or whose round carries no
    # scope: every scope metric is left out
    for paths in (None, {k: "jit(traced)/while" for k in
                         map(scopes.instruction,
                             (e[0] for e in fx["devices"]["0"]))}):
        monkeypatch.setattr(scopes, "program_paths", lambda p=paths: p)
        ctx = _ctx(fx)
        for name in ("model.fwd_bwd_ms", "model.attention_ms",
                     "model.head_ms", "flat.flatten_ms",
                     "flat.unflatten_ms", "engine.local_update_ms",
                     "engine.update_roofline", "engine.sync_ms",
                     "device.unscoped_share"):
            assert files.metric_reader(name)(ctx) is None, name


def test_update_roofline_counts_only_vrl_over_sgd(fx, paths):
    ctx = _ctx(fx)
    ctx["traffic"] = {"workers": 1, "vrl": {"inner_optimizer": "adam"}}
    assert files.metric_reader("engine.update_roofline")(ctx) is None


def test_program_paths_read_the_programs_map(monkeypatch):
    from repro.obs import scopemap
    monkeypatch.setattr(scopemap, "_latest", None)
    assert scopes.program_paths() is None

    class Compiled:
        def as_text(self):
            return ('  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, '
                    'metadata={op_name="jit(traced)/engine.sync/add" '
                    'source_file="x.py" source_line=1}\n'
                    '  ROOT %copy.2 = f32[8]{0} copy(%fusion.1)\n')

    scopemap.record(Compiled())
    assert scopes.program_paths() == {
        "fusion.1": "jit(traced)/engine.sync/add"}
    monkeypatch.setattr(scopemap, "_latest", None)
