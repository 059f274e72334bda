"""Device time of the sync's all-reduce per round, in ms: the all-reduce
operations' time on a chip over the rounds traced, the largest over the
chips used."""

from benchlib import xtrace

ALLREDUCE = r"^all-reduce"


def read(ctx):
    tr = ctx["trace"]
    ms = [1e3 * xtrace.op_time_s(tr, dev, ALLREDUCE) / ctx["rounds_traced"]
          for dev in tr["devices"]]
    ms = [x for x in ms if x > 0]
    return max(ms) if ms else None
