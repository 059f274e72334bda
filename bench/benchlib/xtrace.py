"""From the profiler's trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain form that the rest of this module reduces, and that a test can keep
as JSON:

    {"window": [start_ns, end_ns],
     "devices": {"0": [[op name, start_ns, duration_ns], ...], ...},
     "async": {"0": [[op name, start_ns, duration_ns], ...], ...},
     "host": [[span name, start_ns, duration_ns], ...],
     "planes": [[plane name, [[line name, events], ...]], ...]}

``devices`` holds the operations each TPU ran (its "XLA Ops" line) that
overlap the window, ``async`` its "Async XLA Ops" line (copies and
collectives in flight beside them); ``host`` holds the benchmark's own
spans.  The window is the host span named ``window`` that the harness
opens around the traced rounds.  Device and host events share the
profiler's clock.

The TPU names an operation by its whole HLO instruction
(``%fusion.585 = f32[2,14,2048,64]{...} fusion(...), ...``); ``load``
keeps ``<opcode>:<instruction>:<result shape>``, e.g.
``fusion:fusion.585:f32[2,14,2048,64]``.  Instruction numbers change
with the program; opcodes do not.  ``while``, ``conditional`` and
``call`` hold other operations: they count towards busy time but are no
operation of their own anywhere else.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

HOST_SPANS = ("window", "data", "dispatch", "wait")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
CONTAINERS = re.compile(r"^(while|conditional|call):")
_HLO = re.compile(r"^%?(\S+) = (.*?) ([a-z][a-z0-9-]*)\(")


def op_name(hlo: str) -> str:
    """``<opcode>:<instruction>:<result shape>`` of one HLO instruction's
    text (the text itself where it does not parse)."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:120]
    shape = re.sub(r"\{[^{}]*\}", "", m.group(2))[:60]
    return f"{m.group(3)}:{m.group(1)}:{shape}"


def load(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    host, devices, planes, asyncs = [], {}, [], {}
    for plane in pd.planes:
        planes.append([plane.name, [[ln.name, len(list(ln.events))]
                                    for ln in plane.lines]])
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, aops = [], []
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    evs = [[op_name(e.name), float(e.start_ns),
                            float(e.duration_ns)] for e in line.events]
                    (ops if line.name == OPS_LINE else aops).extend(evs)
            devices[m.group(1)] = ops
            asyncs[m.group(1)] = aops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, float(e.start_ns), float(e.duration_ns)]
                         for e in line.events if e.name in HOST_SPANS]
    wins = [h for h in host if h[0] == "window"]
    if not wins:
        raise ValueError("the trace has no host span named 'window'")
    w0, w1 = wins[-1][1], wins[-1][1] + wins[-1][2]
    clip = lambda evs: [e for e in evs if e[1] < w1 and e[1] + e[2] > w0]  # noqa: E731
    return {"window": [w0, w1],
            "devices": {d: clip(ops) for d, ops in devices.items()},
            "async": {d: clip(ops) for d, ops in asyncs.items()},
            "host": [h for h in clip(host) if h[0] != "window"],
            "planes": planes}


# ------------------------------------------------------------- intervals
def _cut(ev, w0, w1):
    return max(ev[1], w0), min(ev[1] + ev[2], w1)


def union(intervals) -> list:
    """Merged, sorted (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _minus(a, b) -> list:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def window_s(t: dict) -> float:
    return (t["window"][1] - t["window"][0]) * 1e-9


def busy_intervals(t: dict, dev: str) -> list:
    w0, w1 = t["window"]
    return union(_cut(ev, w0, w1) for ev in t["devices"][dev])


def busy_s(t: dict) -> dict:
    """Seconds in which any operation ran, per device."""
    return {d: _length(busy_intervals(t, d)) * 1e-9 for d in t["devices"]}


def op_intervals(t: dict, dev: str, pattern: str) -> list:
    w0, w1 = t["window"]
    rx = re.compile(pattern)
    return union(_cut(ev, w0, w1) for ev in t["devices"][dev]
                 if rx.search(ev[0]))


def op_time_s(t: dict, dev: str, pattern: str) -> float:
    """Device seconds of the operations whose name matches ``pattern``
    (overlapping events of one device counted once)."""
    return _length(op_intervals(t, dev, pattern)) * 1e-9


def op_count(t: dict, dev: str, pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(1 for ev in t["devices"][dev] if rx.search(ev[0]))


def exposed_s(t: dict, dev: str, pattern: str) -> float:
    """Seconds of the matching operations during which no other operation
    ran on that device."""
    w0, w1 = t["window"]
    rx = re.compile(pattern)
    mine = union(_cut(ev, w0, w1) for ev in t["devices"][dev]
                 if rx.search(ev[0]))
    rest = union(_cut(ev, w0, w1) for ev in t["devices"][dev]
                 if not rx.search(ev[0]) and not CONTAINERS.match(ev[0]))
    return _length(_minus(mine, rest)) * 1e-9


def top_ops(t: dict, n: int = 10) -> list:
    """[name, seconds] of the operations that took most device time in
    the window, summed over devices and divided by their number
    (containers left out)."""
    w0, w1 = t["window"]
    tot = {}
    for ops in t["devices"].values():
        for ev in ops:
            if CONTAINERS.match(ev[0]):
                continue
            s, e = _cut(ev, w0, w1)
            tot[ev[0]] = tot.get(ev[0], 0.0) + (e - s) * 1e-9
    nd = max(len(t["devices"]), 1)
    return [[k, v / nd] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(t: dict, n: int = 10) -> list:
    """The ``n`` longest idle gaps of device 0 inside the window, as
    [label, seconds]: the label is the host span that overlaps the gap
    most ("none" when no span does)."""
    if not t["devices"]:
        return []
    dev = sorted(t["devices"])[0]
    w0, w1 = t["window"]
    gaps = _minus([[w0, w1]], busy_intervals(t, dev))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, lab = 0.0, "none"
        for name, hs, hd in t["host"]:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best:
                best, lab = ov, name
        out.append([lab, (e - s) * 1e-9])
    return out
