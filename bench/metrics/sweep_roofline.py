"""Factorizer sweep: the least time of one sweep's work over its measured
device time.

The work of one sweep at the engine's slot count comes from the
configuration's ``sweep_work`` (counted from the algorithm and its shapes,
not from the program); its least time on the chip is the larger of
operations over the bf16 peak and bytes over the HBM bandwidth
(``bench/peaks.json``).  The measured time is the device time of the sweep
program's executions (``jit_run_sweeps``) in the traced window over the
sweeps the engine ran in it.
"""

from bench import trace as tr

PROGRAM = "jit_run_sweeps"


def read(ctx):
    if ctx.sweeps <= 0 or not ctx.planes:
        return None
    ns = sum(tr.program_ns(ctx.trace, p, PROGRAM, ctx.lo, ctx.hi)[0]
             for p in ctx.planes)
    if ns <= 0:
        return None
    flops, nbytes = ctx.sweep_work(ctx.slots)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9 / ctx.sweeps)
