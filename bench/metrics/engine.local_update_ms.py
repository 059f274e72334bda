"""Device time of the k local updates per round, in ms: the fused update
kernel and what runs beside it, under the named scope
``engine.local_update``, the largest over the chips used."""

from benchlib import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "engine.local_update")
