"""Everything of a cell is a file found by the name ``BENCHMARK.json``
gives it: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<cell>.json``, ``metrics/<metric>.py`` and
``reference/<reference>.py``, named by the traffic file or else by the
configuration.  A later cell adds files; none is edited.

A reference module is the plain float32 model and algorithm, and the
model's counts.  It gives:

    dims(cfg)                  the sizes it reads from a configuration's
                               published keys
    seed_key(seed)             a PRNG key from any whole-number seed
    params_from_key(dims, key) the benchmark's weights (traceable)
    init_params(dims, seed)    the same, made in one jitted call
    check(vrl)                 ValueError where it cannot follow the
                               program's ``VRLConfig``
    Reference(dims, vrl, *, workers, devices, dtype, half_batch,
              no_exchange)     ``.run(params0, rounds)``: the readings
    param_count(dims)          every trained parameter
    train_flops_per_token(dims, seq)
                               FLOP one token needs in a training step

and may give ``make_readout`` and ``compare`` (``cell.Program``).  A
configuration of another architecture brings its own module, counts
included (``counts``)."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent


class BenchError(Exception):
    """The benchmark cannot run: a file or a device is missing."""


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing {path}") from None


def benchmark() -> dict:
    return load_json(REPO / "BENCHMARK.json")


def cell(spec: dict, name: str) -> dict:
    for c in spec["workloads"]:
        if c["name"] == name:
            return c
    raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[c['name'] for c in spec['workloads']]}")


def config(cell: dict) -> dict:
    return load_json(BENCH / "configs" / f"{cell['config']}.json")


def traffic(cell: dict) -> dict:
    return load_json(BENCH / "traffic" / f"{cell['traffic']}.json")


def limits(cell: dict) -> dict:
    return load_json(BENCH / "limits" / f"{cell['name']}.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a chip not in the table is an error."""
    table = load_json(BENCH / "benchlib" / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"no peaks for {device_kind!r} in peaks.json")
    return table[device_kind]


def _module(path: Path, name: str):
    if not path.exists():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cfg: dict, traffic: dict | None = None):
    """The plain reference module that the traffic file names (a program
    setting of its own, such as another algorithm, needs a reference of
    its own), or else, and with no traffic, the one the configuration
    names."""
    name = (traffic or {}).get("reference", cfg["reference"])
    return _module(BENCH / "reference" / f"{name}.py", f"bench_ref_{name}")


def metric_reader(name: str):
    """``metrics/<name>.py``: ``read(ctx) -> float | None``."""
    mod = _module(BENCH / "metrics" / f"{name}.py",
                  "bench_metric_" + name.replace(".", "_"))
    return mod.read


def cell_metrics(spec: dict, cell_name: str, kind: str) -> list:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") this cell
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in spec[kind]
            if cell_name in m.get("workloads", [cell_name])]
