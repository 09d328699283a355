"""The whole round's share of the chips' peak: the operations the rounds
of the traced window required (``bench.counts``) over the window's
seconds, chips and peak bf16 rate (round program layer; moves
``rounds_per_s``)."""


def read(ctx):
    flops = ctx.work["flops"]
    if flops <= 0:
        return None
    return 100.0 * flops / ctx.trace.window_s / (ctx.chips * ctx.peaks["bf16_flops"])
