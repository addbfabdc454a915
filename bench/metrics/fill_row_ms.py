"""Engine fill (``Engine._fill`` in engine/engine.py): wall time of a fill
per row it slotted, in milliseconds.

Σ``fill`` duration / Σ``rows`` over the engine's ``fill`` spans that start
inside the measured window.  A ``fill`` span covers the choice of rows from
the queue, the host-side assembly of the padded batch and the device
scatter; a fill that found no free slot counts its time with 0 rows.
"""


def read(ctx):
    wall = rows = 0.0
    for sp in ctx.spans:
        if sp.track != ctx.engine or sp.name != "fill" or sp.instant \
                or sp.t1 is None or not ctx.host0 <= sp.t0 <= ctx.host1 \
                or "rows" not in sp.args:
            continue
        wall += sp.t1 - sp.t0
        rows += sp.args["rows"]
    if rows <= 0:
        return None
    return 1e3 * wall / rows
