"""LM decode burst (``LMEngine.step`` in runtime/lm.py): the share of
row-steps the decode bursts spend on occupied slots.

Σ(``live`` x ``steps``) / Σ(``slots`` x ``steps``) over the engine's
``decode-burst`` spans that start inside the measured window; ``live`` is
the number of occupied slots when the burst starts.  A row that finishes
inside a burst still takes its slot's row-steps until the burst ends and
the retirement scan frees it, so this reads the same as the occupancy a
burst starts with: what it shows is how full admission keeps the slots.
"""


def read(ctx):
    live = total = 0
    for sp in ctx.spans:
        if sp.track != ctx.engine or sp.name != "decode-burst" \
                or not ctx.host0 <= sp.t0 <= ctx.host1:
            continue
        args = sp.args
        if not {"live", "slots", "steps"} <= set(args):
            continue
        live += args["live"] * args["steps"]
        total += args["slots"] * args["steps"]
    if total <= 0:
        return None
    return 100.0 * live / total
