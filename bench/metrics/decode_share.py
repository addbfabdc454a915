"""Engine decode (``Engine._retire`` in engine/engine.py): the share of
engine-step time spent decoding retired rows.

Σ``decode`` / Σ``step`` over the window (``bench/phases.py``).  A
``decode`` span covers the whole-batch decode program, its pull to the host
and the per-row results taken from it.
"""

from bench.phases import step_share


def read(ctx):
    return step_share(ctx, "decode")
