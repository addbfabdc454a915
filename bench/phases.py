"""Shares of engine-step time by phase, read from the program's spans.

A phase's share is the time in the engine's spans of that name over the
time in its ``step`` spans, each counted over the part that lies inside the
measured window ``[ctx.host0, ctx.host1]``, as ``host_share`` counts.
"""


def step_share(ctx, phase: str):
    """100 x Σ``phase`` / Σ``step`` on the engine's track, or ``None`` when
    the window holds no step time or no span named ``phase``."""
    step = part = 0.0
    seen = False
    for sp in ctx.spans:
        if sp.track != ctx.engine or sp.instant or sp.t1 is None \
                or sp.name not in ("step", phase):
            continue
        inside = min(sp.t1, ctx.host1) - max(sp.t0, ctx.host0)
        if inside <= 0:
            continue
        if sp.name == "step":
            step += inside
        else:
            part += inside
            seen = True
    if step <= 0 or not seen:
        return None
    return 100.0 * part / step
