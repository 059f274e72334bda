"""A configuration and traffic small enough for the CPU tests: the
qwen2-0.5b file's block (QKV bias, GQA, SwiGLU, tied head) at toy
widths."""
from __future__ import annotations

import copy

from benchlib import files

DIMS = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=512)
PROGRAM = dict(d_model=64, d_ff=128, num_layers=2, num_heads=4,
               num_kv_heads=2, head_dim=16, vocab_size=512)


def config() -> dict:
    cfg = copy.deepcopy(files.load_json(files.BENCH / "configs"
                                        / "qwen2-0.5b.json"))
    cfg.update(DIMS)
    cfg["program"].update(PROGRAM)
    return cfg


def traffic(workers: int = 1, **kw) -> dict:
    t = dict(workers=workers, batch=2, seq=32, k=2, rounds=8, alpha=0.05,
             lr=0.05, follow=2)
    t.update(kw)
    return t
