"""Runtime layer: the share of request wall time spent waiting to be
admitted.

Over the runtime's request spans, the time inside the measured window
spent between submit and the request's ``admit`` instant, over the time
inside the window spent between submit and answer: the ``queue_wait`` bucket of
the program's span attribution, as one sum over the window.
"""


def read(ctx):
    admit = {}
    for sp in ctx.spans:
        if sp.track == "requests" and sp.name == "admit" and sp.instant \
                and sp.parent is not None:
            admit[sp.parent] = sp.t0
    wait = wall = 0.0
    for sp in ctx.spans:
        if sp.track != "requests" or sp.name != "request" or sp.instant:
            continue
        end = ctx.host1 if sp.t1 is None else min(sp.t1, ctx.host1)
        start = max(sp.t0, ctx.host0)
        if end <= start:
            continue
        wall += end - start
        wait += max(min(admit.get(sp.sid, end), end) - start, 0.0)
    if wall <= 0:
        return None
    return 100.0 * wait / wall
