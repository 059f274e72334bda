"""Device time of the sync's all-reduce per round, in ms: the all-reduce
operations under the named scope ``engine.sync`` (not the loss mean's,
which has no scope), their time on a chip over the rounds traced, the
largest over the chips used.  None where the round runs no such
all-reduce (one worker) or the program keeps no scope map."""

from benchlib import scopes, xtrace

ALLREDUCE = r"^all-reduce"


def read(ctx):
    pattern = scopes.ops_under(ctx, ALLREDUCE, "engine.sync")
    if pattern is None:
        return None
    tr = ctx["trace"]
    return max(1e3 * xtrace.op_time_s(tr, dev, pattern)
               / ctx["rounds_traced"] for dev in tr["devices"])
