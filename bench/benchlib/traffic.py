"""The token stream of a cell, from its traffic file and ``--seed``.

A copy of the program's Dirichlet-skewed generator
(``repro.data.synthetic.assigned_token_stream`` with one shard per
worker): worker w draws its tokens from its own unigram distribution,
Dirichlet(alpha) over the whole vocabulary, and each sequence is sorted
so that runs of equal tokens make the next token predictable.  Small
alpha makes the workers' data far from identical, the paper's regime.

The traffic file gives the sizes:

    workers       W, one per chip on a mesh, or one alone
    batch, seq    sequences of ``seq`` tokens per worker step
    k             local steps per round
    rounds        distinct rounds drawn; the window cycles through them
    alpha         Dirichlet concentration
    lr            the learning rate
    follow        rounds at the start that the reference follows

and may give

    vrl           the program's settings over ``cell.DEFAULTS``: any
                  ``VRLConfig`` field, as ``launch/train.py``'s flags set
                  it (``{"overlap": true}``, ``{"compress": "int8"}``,
                  ``{"inner_optimizer": "adam", "moment_dtype":
                  "bfloat16"}``, ``{"algorithm": "hier_vrl_sgd", "hier":
                  {"pods": 2, "k1": 2, "k2": 8}}``)
    reference     the module under ``reference/`` that follows those
                  settings, where the configuration's does not
"""
from __future__ import annotations

import numpy as np

KEYS = ("workers", "batch", "seq", "k", "rounds", "alpha", "lr", "follow")


def check(t: dict) -> dict:
    missing = [k for k in KEYS if k not in t]
    if missing:
        raise ValueError(f"traffic file lacks {missing}")
    if t["follow"] > t["rounds"] or t["follow"] < 1:
        raise ValueError("follow must be within 1..rounds")
    return t


def rng_for(seed: int) -> np.random.RandomState:
    """Any non-negative whole number seeds the stream; 64-bit seeds do
    not wrap onto 32-bit ones."""
    return np.random.RandomState(
        np.random.SeedSequence(int(seed)).generate_state(4))


def token_pool(t: dict, vocab: int, seed: int) -> np.ndarray:
    """(rounds, k, W, batch, seq) int32: every round the cell feeds."""
    w, steps = t["workers"], t["rounds"] * t["k"]
    rng = rng_for(seed)
    probs = rng.dirichlet([t["alpha"]] * vocab, size=w)
    out = np.empty((steps, w, t["batch"], t["seq"]), np.int32)
    for u in range(w):
        draws = rng.choice(vocab, size=(steps, t["batch"], t["seq"]),
                           p=probs[u])
        out[:, u] = np.sort(draws, axis=-1)
    return out.reshape((t["rounds"], t["k"]) + out.shape[1:])


def tokens_per_round(t: dict) -> int:
    return t["workers"] * t["batch"] * t["seq"] * t["k"]
