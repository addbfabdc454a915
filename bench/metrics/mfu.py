"""Whole request step: useful operations over the chip's bf16 peak.

Useful operations are, over the requests answered inside the measured
window, the sweeps each of their queries ran times the operations of one
query's sweep (the configuration's ``row_flops``); sweeps of parked rows
and a burst's overshoot do not count.  Divided by window x chips x the
bf16 peak of ``bench/peaks.json``.
"""


def read(ctx):
    window_s = ctx.host1 - ctx.host0
    if ctx.row_sweeps <= 0 or window_s <= 0:
        return None
    flops = ctx.row_sweeps * ctx.row_flops
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips * window_s
    return 100.0 * flops / peak
