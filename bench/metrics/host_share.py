"""Engine layer: the share of engine-step time spent on the host.

(time in ``step`` spans - time in ``sweep-burst`` spans) / time in
``step`` spans, counting the part of each of the engine's spans that lies
inside the measured window.  A burst span ends on the host sync after the sweeps, so it
covers their device time; the rest of a step is fill, retire, the decode
pull and the postprocess.
"""


def read(ctx):
    step = burst = 0.0
    for sp in ctx.spans:
        if sp.track != ctx.engine or sp.instant or sp.t1 is None:
            continue
        inside = min(sp.t1, ctx.host1) - max(sp.t0, ctx.host0)
        if inside <= 0:
            continue
        if sp.name == "step":
            step += inside
        elif sp.name == "sweep-burst":
            burst += inside
    if step <= 0:
        return None
    return 100.0 * (step - burst) / step
