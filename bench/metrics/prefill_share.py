"""LM chunked prefill (``ServeEngine.add_request`` in launch/serve.py, paged):
the share of engine-step time spent prefilling admitted prompts.

Σ``prefill-chunk`` / Σ``step`` over the window (``bench/phases.py``); one
``prefill-chunk`` span per chunk dispatch, inside the step's ``fill``.
"""

from bench.phases import step_share


def read(ctx):
    return step_share(ctx, "prefill-chunk")
