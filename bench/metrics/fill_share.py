"""Engine fill (``Engine._fill`` in engine/engine.py): the share of
engine-step time spent filling free slots.

Σ``fill`` / Σ``step`` over the window (``bench/phases.py``).  A ``fill``
span covers the choice of rows from the queue, the host-side assembly of
the padded batch (the per-row copies) and the device scatter.
"""

from bench.phases import step_share


def read(ctx):
    return step_share(ctx, "fill")
