"""Operations and parameters the work requires: the numerator of model
FLOP utilization (``round.mfu``) and the bytes of the engine's update
(``engine.update_roofline``).

A count belongs to the model, so it comes from the reference module that
the configuration names (``cfg["reference"]``; a traffic file's own
reference follows another algorithm, not another model), applied to that
module's ``dims`` of the configuration.  ``files`` states the contract.
A reference that gives no count is an error: no formula of another
model's stands in for it."""
from __future__ import annotations

from benchlib import files


def _count(cfg: dict, name: str, *args) -> int:
    ref = files.reference(cfg)
    fn = getattr(ref, name, None)
    if fn is None:
        raise files.BenchError(
            f"the reference module {ref.__name__} "
            f"(reference/{cfg['reference']}.py) gives no {name}(dims"
            f"{', ...' if args else ''}), which the benchmark's counts "
            f"need")
    return int(fn(ref.dims(cfg), *args))


def param_count(cfg: dict) -> int:
    """Every trained parameter (a tied embedding counted once)."""
    return _count(cfg, "param_count")


def train_flops_per_token(cfg: dict, seq: int) -> int:
    """FLOP one token needs in a training step at ``seq`` tokens a
    sequence, forward and backward; recomputation is not required, so it
    is not counted."""
    return _count(cfg, "train_flops_per_token", seq)


def matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix product per token, where the reference
    counts them apart (the dense one does)."""
    return _count(cfg, "matmul_params")
