"""Kernels: the fused resonator kernel's share of its roofline.

The least time of one sweep's work at the engine's slot count (as for
``sweep_roofline``: the larger of the configuration's operations over the
bf16 peak and its bytes over the HBM bandwidth) over the device time of
one call of the kernel, read from the kernel's own events in the trace.
"""

from bench import trace as tr

KERNEL = "resonator_step"


def read(ctx):
    ns = calls = 0
    for p in ctx.planes:
        t, c = tr.kernel_ns(ctx.trace, p, KERNEL, ctx.lo, ctx.hi)
        ns, calls = ns + t, calls + c
    if calls == 0 or ns <= 0:
        return None
    flops, nbytes = ctx.sweep_work(ctx.slots)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9 / calls)
