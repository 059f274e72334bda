"""The trace reduction against hand counts."""
import json

import pytest

from benchlib import xtrace

# window 0..1000 ns on one device:
#   A 100-200 and B 150-250 overlap: busy 100-250
#   all-reduce.1 400-500, with C 450-470 running beside it
#   D 900-1100 runs past the window's end: cut at 1000
SYNTH = {
    "window": [0.0, 1000.0],
    "devices": {"0": [["A.1", 100.0, 100.0], ["B", 150.0, 100.0],
                      ["all-reduce.1", 400.0, 100.0], ["C", 450.0, 20.0],
                      ["D.7", 900.0, 200.0]]},
    "host": [["data", 260.0, 40.0], ["dispatch", 300.0, 90.0],
             ["wait", 520.0, 360.0]],
}


def test_busy_and_idle_hand_counts():
    # busy: 150 + 100 + 100 = 350 ns of the 1000 ns window
    assert xtrace.busy_s(SYNTH) == {"0": pytest.approx(350e-9)}
    assert xtrace.window_s(SYNTH) == pytest.approx(1000e-9)


def test_collective_time_and_exposed_part():
    assert xtrace.op_time_s(SYNTH, "0", "^all-reduce") == pytest.approx(
        100e-9)
    assert xtrace.op_count(SYNTH, "0", "^all-reduce") == 1
    # C covers 20 of the all-reduce's 100 ns
    assert xtrace.exposed_s(SYNTH, "0", "^all-reduce") == pytest.approx(
        80e-9)


def test_top_ops_and_idle_gaps():
    top = dict(xtrace.top_ops(SYNTH))
    assert top["D.7"] == pytest.approx(100e-9)      # cut at the window
    assert top["A.1"] == pytest.approx(100e-9)
    assert top["all-reduce.1"] == pytest.approx(100e-9)
    # gaps: 500-900 (400 ns, under "wait"), 250-400 (150 ns, mostly
    # "dispatch"), 0-100 (100 ns, no span)
    assert xtrace.idle_gaps(SYNTH) == [
        ["wait", pytest.approx(400e-9)], ["dispatch", pytest.approx(150e-9)],
        ["none", pytest.approx(100e-9)]]


def test_op_names_and_containers():
    hlo = ("%fusion.585 = f32[2,14,2048,64]{2,3,1,0:T(8,128)} "
           "fusion(f32[2,14,2048,64]{2,3,1,0:T(8,128)} %p), kind=kLoop")
    assert xtrace.op_name(hlo) == "fusion:fusion.585:f32[2,14,2048,64]"
    loop = ("%while.239 = (s32[]{:T(128)}, f32[8]{0:T(128)}) "
            "while((s32[]{:T(128)}, f32[8]{0:T(128)}) %tuple.241)")
    assert xtrace.op_name(loop) == "while:while.239:(s32[], f32[8])"
    # a loop around the collective hides none of it
    t = {"window": [0.0, 100.0],
         "devices": {"0": [[xtrace.op_name(loop), 0.0, 100.0],
                           ["all-reduce:all-reduce.1:f32[8]", 10.0, 50.0]]},
         "host": []}
    assert xtrace.exposed_s(t, "0", "^all-reduce") == pytest.approx(50e-9)
    assert [n for n, _ in xtrace.top_ops(t)] == [
        "all-reduce:all-reduce.1:f32[8]"]


def test_union_and_minus():
    assert xtrace.union([(5, 7), (1, 3), (2, 4), (8, 8)]) == [[1, 4], [5, 7]]
    assert xtrace._minus([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]


def _recorded():
    import json
    from benchlib import files
    with open(files.BENCH / "testdata" / "round_boundary.json") as f:
        return json.load(f)


def test_recorded_chip_trace_hand_counts():
    """27 ms of a TPU v5e trace (qwen2-0.5b.s2048.k8) around the end of a
    round: the scan's last local update, the sync kernel between two
    param-sized copies, then the next round's first ops.  Counted by hand
    on a 1 ns grid: 6,011 ns idle, in gaps of 3,432, 2,555, 16, 2, 2, ...
    ns; the two Pallas kernels (their instruction names in this build)
    run 198,496 + 14,704,773 ns inside the window."""
    t = _recorded()
    assert xtrace.window_s(t) == pytest.approx(0.027)
    assert xtrace.busy_s(t)["0"] == pytest.approx(26_993_989e-9)
    kernels = r"^custom-call:(closed_call|traced)"
    assert xtrace.op_time_s(t, "0", kernels) == pytest.approx(14_903_269e-9)
    assert xtrace.op_count(t, "0", kernels) == 2
    # nothing but the enclosing containers runs beside the sync kernel
    assert xtrace.exposed_s(t, "0", r"^custom-call:traced") == pytest.approx(
        14_704_773e-9)
    gaps = xtrace.idle_gaps(t, 3)
    assert [g[1] for g in gaps] == [pytest.approx(3432e-9),
                                    pytest.approx(2555e-9),
                                    pytest.approx(16e-9)]
    assert xtrace.top_ops(t, 1)[0][0].startswith("custom-call:traced")


# the all-reduces' paths as the program's map gives them on a four-worker
# mesh: the sync's psum under ``engine.sync``, the loss mean's under none
SYNC_PSUM = "jit(traced)/shard_map/engine.sync/psum"
LOSS_MEAN = "jit(traced)/while/body/closed_call/reduce_sum"


def _program_paths(monkeypatch, paths):
    from benchlib import scopes
    monkeypatch.setattr(scopes, "program_paths", lambda: paths)


def test_recorded_allreduce_hand_counts(monkeypatch):
    """0.4 ms around a sync all-reduce on four TPU v5e chips
    (qwen2-0.5b.w4.s512.k2), chips 0 and 1: the psum of the 1930240 x 256
    flat buffer runs 34,696,404 ns on chip 0 and 34,730,812 ns on chip 1
    with nothing beside it but the enclosing loop, so all of it is
    exposed; each chip is idle 19 ns of the 35,096,404 ns window.  The
    sync readers find the psum by its path under ``engine.sync``."""
    from benchlib import files
    _program_paths(monkeypatch, {"psum.7": SYNC_PSUM})
    with open(files.BENCH / "testdata" / "allreduce.json") as f:
        t = json.load(f)
    assert xtrace.op_time_s(t, "0", "^all-reduce") == pytest.approx(
        34_696_404e-9)
    assert xtrace.exposed_s(t, "1", "^all-reduce") == pytest.approx(
        34_730_812e-9)
    assert xtrace.busy_s(t) == {"0": pytest.approx(35_096_385e-9),
                                "1": pytest.approx(35_096_385e-9)}
    ctx = {"trace": t, "rounds_traced": 1, "busy_s": xtrace.busy_s(t),
           "window_s": xtrace.window_s(t)}
    # the readers take the worst chip
    assert files.metric_reader("sync.allreduce_ms")(ctx) == pytest.approx(
        34.730812)
    assert files.metric_reader("sync.exposed_ms")(ctx) == pytest.approx(
        34.730812)
    assert files.metric_reader("device.idle_share")(ctx) == pytest.approx(
        100 * 19 / 35_096_404)


def test_sync_readers_count_only_the_sync_allreduce(monkeypatch):
    """Two chips, each running the sync's all-reduce (100 ns, 20 and 11 of
    them beside a fusion) and the loss mean's (40 and 50 ns): the readers
    count the sync's alone.  Without a scope map, or where no all-reduce
    lies under ``engine.sync`` (one worker), they read nothing."""
    from benchlib import files
    ops = lambda d: [                                       # noqa: E731
        ["all-reduce:psum.7:f32[8]", 100.0 + d, 100.0],
        ["fusion:fusion.3:f32[8]", 180.0 + 10 * d, 40.0],
        ["all-reduce:all-reduce.12:f32[]", 400.0, 40.0 + 10 * d]]
    t = {"window": [0.0, 1000.0], "devices": {"0": ops(0), "1": ops(1)},
         "host": []}
    ctx = {"trace": t, "rounds_traced": 2}
    read = lambda name: files.metric_reader(name)(ctx)     # noqa: E731
    _program_paths(monkeypatch, {"psum.7": SYNC_PSUM,
                                 "all-reduce.12": LOSS_MEAN,
                                 "fusion.3": "jit(traced)/model/add"})
    # 100 ns over 2 rounds; exposed: chip 0 80 ns, chip 1 89 ns
    assert read("sync.allreduce_ms") == pytest.approx(50e-6)
    assert read("sync.exposed_ms") == pytest.approx(44.5e-6)
    for paths in (None, {"all-reduce.12": LOSS_MEAN}):
        _program_paths(monkeypatch, paths)
        assert read("sync.allreduce_ms") is None
        assert read("sync.exposed_ms") is None
