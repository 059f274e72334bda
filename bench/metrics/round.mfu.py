"""Model FLOP utilization of the round: the run's tokens/s times the FLOP
a token requires in training (``benchlib.counts``; remat's recomputation
not counted) over the chips' bf16 peak, in percent."""


def read(ctx):
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * ctx["tokens_per_s"] * ctx["flops_per_token"] / peak
