"""Engine postprocess (``Engine._finalize``, then the configuration's
``postprocess`` in engine/pipelines.py): the share of engine-step time
spent finishing answered requests.

Σ``postprocess`` / Σ``step`` over the window (``bench/phases.py``); one
``postprocess`` span per finished request.
"""

from bench.phases import step_share


def read(ctx):
    return step_share(ctx, "postprocess")
