"""Device layer: the share of the traced window in which no operation ran
on the device, averaged over the cell's chips (profiler trace)."""

from bench import trace as tr


def read(ctx):
    if not ctx.planes or ctx.hi <= ctx.lo:
        return None
    return 100.0 * (1.0 - tr.busy_share(ctx.trace, ctx.lo, ctx.hi))
