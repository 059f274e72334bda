"""Device time of the head and the loss per round, in ms: the final norm,
the vocabulary product and the cross-entropy, under the named scope
``head`` (forward and backward), the largest over the chips used."""

from benchlib import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "head")
