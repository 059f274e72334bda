"""Device time of the gradients' flatten into the (W, R, C) buffer per
round, in ms: the named scope ``flat.flatten``, the largest over the chips
used."""

from benchlib import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "flat.flatten")
