"""Share of the traced window in which the busiest-idle chip ran no
operation: 1 - (union of its op intervals) / window, the largest over the
chips used, in percent."""


def read(ctx):
    w = ctx["window_s"]
    if not ctx["busy_s"] or w <= 0:
        return None
    return 100.0 * max(1.0 - b / w for b in ctx["busy_s"].values())
