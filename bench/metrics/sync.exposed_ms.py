"""The part of the sync's all-reduce per round during which no other
operation ran on that chip, in ms, the largest over the chips used."""

from benchlib import xtrace

ALLREDUCE = r"^all-reduce"


def read(ctx):
    tr = ctx["trace"]
    if not any(xtrace.op_count(tr, d, ALLREDUCE) for d in tr["devices"]):
        return None
    return max(1e3 * xtrace.exposed_s(tr, dev, ALLREDUCE)
               / ctx["rounds_traced"] for dev in tr["devices"])
