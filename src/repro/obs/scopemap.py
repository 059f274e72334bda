"""The named scope of each operation of the compiled round.

``jax.named_scope`` reaches the compiled HLO as each instruction's
``metadata={op_name="..."}``, e.g. ``jit(traced)/while/body/closed_call/
vmap(transpose(jvp(model)))/checkpoint/attention/dot_general``.  A device
trace names an operation by its instruction alone (``%fusion.585 = ...``),
so splitting a trace by scope needs the executable's HLO beside it.

``core.engine.RoundCache`` records each round executable it compiles here;
``op_paths`` maps the instructions of the newest one to their ``op_name``.
The chip benchmark's scope metrics (``bench/benchlib/scopes.py``) read it
after their traced rounds.  Only the newest executable is held, and its
HLO text is printed and parsed on the first ``op_paths`` call, never on the
round's path.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

_NAME = re.compile(r"\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_HEADER = re.compile(r"(?:ENTRY\s+)?%?([^\s(]+)\s*\(")
_CALLS = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)"
                    r"|\bbranch_computations=\{([^}]*)\}")
_OP_NAME = 'op_name="'

_latest = None          # the newest recorded jax.stages.Compiled
_paths: Optional[Dict[str, str]] = None     # its parsed op_paths


def record(compiled) -> None:
    """Hold ``compiled`` as the newest round executable."""
    global _latest, _paths
    _latest, _paths = compiled, None


def latest():
    """The newest recorded executable, or None."""
    return _latest


def _lines(hlo_text: str):
    """The HLO text's lines, an instruction whose attributes run over
    several lines (a Pallas kernel's ``kernel_metadata``) joined into one:
    its ``op_name`` follows them."""
    held = None
    for line in hlo_text.splitlines():
        if held is not None:
            held += line
            if held.count("{") <= held.count("}"):
                yield held
                held = None
        elif line.startswith(" ") and line.count("{") > line.count("}"):
            held = line
        else:
            yield line
    if held is not None:
        yield held


def parse(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of an HLO module's text.  An instruction
    the compiler made without an ``op_name`` (a loop's layout copy) takes
    that of the instruction that calls its computation, as a profiler's
    ``tf_op`` does; one in the entry computation has none and is left
    out."""
    own, home, caller = {}, {}, {}
    comp = None
    for line in _lines(hlo_text):
        if not line.startswith(" "):
            m = _HEADER.match(line)
            if m and line.rstrip().endswith("{"):
                comp = m.group(1)
            continue
        m = _NAME.match(line)
        if not m:
            continue
        name = m.group(1)
        home[name] = comp
        i = line.find(_OP_NAME)
        if i >= 0:
            j = i + len(_OP_NAME)
            own[name] = line[j:line.find('"', j)]
        for one, many in _CALLS.findall(line):
            for callee in [one] if one else re.findall(r"[\w.\-]+", many):
                caller.setdefault(callee, name)
    out = {}
    for name in home:
        seen, at = set(), name
        while at not in own and home.get(at) in caller and at not in seen:
            seen.add(at)
            at = caller[home[at]]
        if at in own:
            out[name] = own[at]
    return out


def op_paths() -> Optional[Dict[str, str]]:
    """``parse`` of the newest recorded executable's HLO, or None where no
    round was compiled."""
    global _paths
    if _latest is None:
        return None
    if _paths is None:
        _paths = parse(_latest.as_text())
    return _paths


def attention_executor(paths: Optional[Dict[str, str]] = None
                       ) -> Optional[Dict[str, object]]:
    """Which attention core the newest recorded round (or ``paths``, an
    ``op_paths``/``parse`` result) runs: ``{"executor": "flash", "kernels":
    n}`` for n flash (``splash_*``) kernel instructions under the
    ``attention`` scope, ``"dense"`` for an ``attention`` scope without
    them, ``"none"`` for a round without attention; None where no round
    was compiled."""
    paths = op_paths() if paths is None else paths
    if paths is None:
        return None
    scoped = [name for name, path in paths.items()
              if "attention" in path.split("/")]
    n = sum(name.startswith("splash_") for name in scoped)
    executor = "flash" if n else ("dense" if scoped else "none")
    return {"executor": executor, "kernels": n}
