"""From the traced rounds to device time per named scope of the round.

The program names its layers with ``jax.named_scope``; XLA keeps the
name stack of each operation in its HLO ``op_name`` metadata, e.g.
``jit(traced)/while/body/closed_call/vmap(transpose(jvp(model)))/while/
body/closed_call/checkpoint/attention/dot_general``.  The trace names an
operation by its instruction (``xtrace.op_name``:
``fusion:fusion.585:f32[2,14,2048,64]``), and the program keeps the
compiled round's {instruction: op_name} in ``repro.obs.scopemap``, which
``program_paths`` reads after the traced rounds.  A program without that
map gives None, and every scope metric is then left out.  An operation the
compiler made without metadata (a layout copy, a concatenate rewritten in
place) takes the path of the loop that runs it; at the round's top level it
has none.

A scope matches an operation when one component of its path, split at
``/`` (and at ``;``, where XLA joined the paths of merged operations),
equals the scope once transform wrappers such as ``jvp(...)``,
``transpose(...)`` and ``vmap(...)`` are peeled off.  A fusion carries its
root's ``op_name``.  ``TOP`` are the five scopes that partition the round;
``NESTED`` lie inside ``model``.  Containers (``while``, ``conditional``,
``call``) are attributed to no scope: their time beyond their body's
operations is unscoped, as is every operation that matches no ``TOP``
scope.  So per device, the ``TOP`` scopes' seconds plus the unscoped
seconds are the busy seconds, up to operations of two scopes that
overlap in time.
"""
from __future__ import annotations

import functools
import re

from benchlib import xtrace

TOP = ("model", "flat.flatten", "flat.unflatten", "engine.local_update",
       "engine.sync")
NESTED = ("attention", "head")
_WRAPPER = re.compile(r"^[A-Za-z_][\w.]*\((.*)\)$")


def peel(component: str) -> str:
    """``vmap(transpose(jvp(model)))`` -> ``model``."""
    m = _WRAPPER.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPER.match(component)
    return component


@functools.lru_cache(maxsize=None)
def components(path: str) -> tuple:
    return tuple(peel(c) for c in re.split(r"[/;]", path) if c)


@functools.lru_cache(maxsize=None)
def top_scope(path: str):
    """The first ``TOP`` scope along the path, or None."""
    for c in components(path):
        if c in TOP:
            return c
    return None


def instruction(op: str) -> str:
    """The instruction of an ``xtrace.op_name``: ``fusion.585``."""
    parts = op.split(":")
    return parts[1] if len(parts) >= 3 else ""


def program_paths():
    """{instruction: op_name} of the program's newest compiled round, or
    None where the program keeps no such map."""
    try:
        from repro.obs import scopemap
    except ImportError:
        return None
    return scopemap.op_paths()


def _intervals(t: dict, dev: str, paths: dict, keep) -> list:
    """Merged intervals of the device's non-container ops whose path
    ``keep`` accepts, cut at the window."""
    w0, w1 = t["window"]
    return xtrace.union((max(e[1], w0), min(e[1] + e[2], w1))
                        for e in t["devices"][dev]
                        if not xtrace.CONTAINERS.match(e[0])
                        and keep(paths.get(instruction(e[0]), "")))


def _length(iv) -> float:
    return sum(e - s for s, e in iv) * 1e-9


def seconds(t: dict, paths: dict) -> dict:
    """Per device of the trace ``t`` (``xtrace.load``'s form), its ops
    given their paths by ``paths``: seconds under each ``TOP`` and
    ``NESTED`` scope (overlapping events counted once), ``busy`` (as
    ``xtrace.busy_s`` counts it, containers included) and ``unscoped``
    (busy time under no ``TOP`` scope)."""
    out = {}
    for dev in t["devices"]:
        busy = xtrace.busy_intervals(t, dev)
        s = {sc: _length(_intervals(t, dev, paths, lambda p, sc=sc:
                                    top_scope(p) == sc)) for sc in TOP}
        s.update({sc: _length(_intervals(t, dev, paths, lambda p, sc=sc:
                                          sc in components(p)))
                  for sc in NESTED})
        scoped = _intervals(t, dev, paths,
                            lambda p: top_scope(p) is not None)
        s["unscoped"] = _length(xtrace._minus(busy, scoped))
        s["busy"] = _length(busy)
        out[dev] = s
    return out


def per_device(ctx: dict) -> dict:
    """``seconds`` of the traced rounds (``ctx["trace"]``) by the
    program's paths, computed once per context (every reader of a run
    shares it)."""
    if "scope_seconds" not in ctx:
        ctx["scope_seconds"] = seconds(ctx["trace"], program_paths() or {})
    return ctx["scope_seconds"]


def ops_under(ctx: dict, pattern: str, scope: str):
    """A pattern for ``xtrace`` that matches exactly the traced operations
    whose name matches ``pattern`` and whose path lies under the ``TOP``
    scope ``scope`` (the sync's all-reduce, not the loss mean's); None
    where no such operation ran, or the program keeps no map."""
    paths = program_paths() or {}
    rx = re.compile(pattern)
    names = sorted({e[0] for ops in ctx["trace"]["devices"].values()
                    for e in ops if rx.search(e[0])
                    and top_scope(paths.get(instruction(e[0]), "")) == scope})
    if not names:
        return None
    return "^(?:" + "|".join(map(re.escape, names)) + ")$"


def ms_per_round(ctx: dict, scope: str):
    """Device ms under ``scope`` per traced round, the largest over the
    chips used; None where no operation of the scope ran (as in a program
    that names no scopes)."""
    ms = [1e3 * s[scope] / ctx["rounds_traced"]
          for s in per_device(ctx).values()]
    ms = [x for x in ms if x > 0]
    return max(ms) if ms else None
