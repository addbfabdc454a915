"""Engine burst planner (``Engine.step`` in engine/engine.py): the share of
row-sweeps spent on occupied slots.

Σ(``live`` x ``sweeps``) / Σ(``slots`` x ``sweeps``) over the engine's
``sweep-burst`` spans that start inside the measured window; ``live`` is
the number of occupied slots when the burst starts.  The rest of the
slots x sweeps a burst runs is spent on empty slots.
"""


def read(ctx):
    live = total = 0
    for sp in ctx.spans:
        if sp.track != ctx.engine or sp.name != "sweep-burst" \
                or not ctx.host0 <= sp.t0 <= ctx.host1:
            continue
        args = sp.args
        if not {"live", "slots", "sweeps"} <= set(args):
            continue
        live += args["live"] * args["sweeps"]
        total += args["slots"] * args["sweeps"]
    if total <= 0:
        return None
    return 100.0 * live / total
