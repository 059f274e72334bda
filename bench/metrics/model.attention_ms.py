"""Device time of attention's (..., H, S, S) core per round, in ms: scores,
mask, softmax and P·V, under the named scope ``attention`` (forward,
recomputation and backward), the largest over the chips used."""

from benchlib import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "attention")
