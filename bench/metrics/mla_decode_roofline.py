"""Kernels (``kernels/flash_decode``, latent pools): the latent decode
kernel's share of its roofline.

The work is the cached (row, position) pairs the decode steps read in the
traced tail: Σ ``kv_tokens`` of the engine's ``decode-burst`` spans, each
counted for the part of the span that lies in the tail.  Its least time is
the larger of the configuration's ``sweep_work`` operations over the bf16
peak and its bytes over the HBM bandwidth; the measured time is the device
time of the kernel's calls (``mla_decode``) in the tail.
"""

from bench import trace as tr

KERNEL = "mla_decode"


def read(ctx):
    ns = calls = 0
    for p in ctx.planes:
        t, c = tr.kernel_ns(ctx.trace, p, KERNEL, ctx.lo, ctx.hi)
        ns, calls = ns + t, calls + c
    if calls == 0 or ns <= 0:
        return None
    t1 = ctx.host1
    t0 = t1 - ctx.window_s
    pairs = 0.0
    for sp in ctx.spans:
        if sp.track != ctx.engine or sp.name != "decode-burst" \
                or sp.t1 is None or "kv_tokens" not in sp.args:
            continue
        inside = min(sp.t1, t1) - max(sp.t0, t0)
        if inside <= 0:
            continue
        length = sp.t1 - sp.t0
        pairs += sp.args["kv_tokens"] * (inside / length if length > 0 else 1)
    if pairs <= 0:
        return None
    flops, nbytes = ctx.sweep_work(pairs)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9)
