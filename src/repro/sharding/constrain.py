"""Runtime activation-sharding helpers (no model imports — cycle-free)."""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P, get_abstract_mesh



def maybe_constrain(x, *spec_parts):
    """with_sharding_constraint iff an ambient mesh with a "model" axis is
    set (no-op in single-device tests). Divisibility-guarded."""
    m = get_abstract_mesh()
    if m.empty or "model" not in m.axis_names:
        return x
    sizes = dict(zip(m.axis_names, m.axis_sizes))
    off = x.ndim - len(spec_parts)
    for i, part in enumerate(spec_parts):
        if part is None:
            continue
        axes = (part,) if isinstance(part, str) else tuple(part)
        need = 1
        for a in axes:
            if a not in sizes:
                return x
            need *= sizes[a]
        if x.shape[off + i] % need:
            return x
    return jax.lax.with_sharding_constraint(x, P(*spec_parts))


def axis_size(name: str) -> int:
    m = get_abstract_mesh()
    if m.empty or name not in m.axis_names:
        return 1 << 30
    return dict(zip(m.axis_names, m.axis_sizes))[name]
