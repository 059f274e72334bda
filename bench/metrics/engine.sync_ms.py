"""Device time of the round's sync, in ms: the worker mean (the all-reduce
on several chips), the Δ-update kernel and its copies, under the named
scope ``engine.sync``, the largest over the chips used."""

from benchlib import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "engine.sync")
