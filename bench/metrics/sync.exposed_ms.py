"""The part of the sync's all-reduce per round during which no other
operation ran on that chip, in ms, the largest over the chips used: the
all-reduce operations under the named scope ``engine.sync``, as
``sync.allreduce_ms`` selects them."""

from benchlib import scopes, xtrace

ALLREDUCE = r"^all-reduce"


def read(ctx):
    pattern = scopes.ops_under(ctx, ALLREDUCE, "engine.sync")
    if pattern is None:
        return None
    tr = ctx["trace"]
    return max(1e3 * xtrace.exposed_s(tr, dev, pattern)
               / ctx["rounds_traced"] for dev in tr["devices"])
