"""The local update's share of the HBM roofline, in percent: the bytes the
update must move per round over its device time per round
(``engine.local_update``, the slowest chip) and the chip's HBM bytes/s.

For VRL-SGD over the inner SGD each local step reads p, g and Δ and
writes p, 4 B each, for every parameter a chip holds: k × (W / chips) ×
``counts.param_count`` × 16 B per round, unpadded.  Another algorithm or
inner optimizer moves other bytes, which are not counted yet: None."""

from benchlib import cell, counts, scopes


def read(ctx):
    vrl = dict(cell.DEFAULTS, **ctx["traffic"].get("vrl", {}))
    if (vrl["algorithm"], vrl["inner_optimizer"]) != ("vrl_sgd", "sgd"):
        return None
    ms = scopes.ms_per_round(ctx, "engine.local_update")
    if ms is None:
        return None
    bytes_ = (ctx["k"] * ctx["workers"] / ctx["chips"]
              * counts.param_count(ctx["config"]) * 16)
    return 100.0 * bytes_ / (ms * 1e-3 * ctx["peaks"]["hbm_bytes_per_s"])
