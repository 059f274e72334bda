"""Device time of the model's forward and backward per round, in ms: the
operations under the named scope ``model`` (embedding, blocks, head and
loss, remat's recomputation included), the largest over the chips used."""

from benchlib import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "model")
