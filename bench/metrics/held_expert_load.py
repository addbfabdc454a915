"""LM expert share (``moe_share`` in nn/moe.py): the tokens each held
expert computes in one decode step.

Σ``held_picks`` / Σ(``steps`` x ``held_experts``) over the engine's
``decode-burst`` spans that start inside the measured window:
``held_picks`` counts the token-expert picks of live decode rows that fell
on the experts held here, over every MoE layer, and ``held_experts`` the
(layer, held expert) pairs.  An expert's weights are read once a step
whatever its load, so the more tokens each sees, the more of that read
does useful work.
"""


def read(ctx):
    picks = pairs = 0
    for sp in ctx.spans:
        if sp.track != ctx.engine or sp.name != "decode-burst" \
                or not ctx.host0 <= sp.t0 <= ctx.host1:
            continue
        args = sp.args
        if not {"held_picks", "held_experts", "steps"} <= set(args):
            continue
        picks += args["held_picks"]
        pairs += args["steps"] * args["held_experts"]
    if pairs <= 0:
        return None
    return picks / pairs
