"""Share of the device's busy time under none of the round's five named
scopes (``model``, ``flat.flatten``, ``flat.unflatten``,
``engine.local_update``, ``engine.sync``), in percent, the largest over
the chips used: what the scope map cannot see.  None where no operation
carries any of them (a program that names no scopes)."""

from benchlib import scopes


def read(ctx):
    per = scopes.per_device(ctx).values()
    if not any(s["unscoped"] < s["busy"] for s in per):
        return None
    return max(100.0 * s["unscoped"] / s["busy"] for s in per
               if s["busy"] > 0)
