"""Device time of the parameters' view from the flat buffer per round, in
ms: the named scope ``flat.unflatten``, the largest over the chips used."""

from benchlib import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "flat.unflatten")
