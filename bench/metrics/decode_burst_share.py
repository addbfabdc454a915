"""LM decode burst (``LMEngine.step`` in runtime/lm.py): the share of
engine-step time spent in the decode steps between retirement scans.

Σ``decode-burst`` / Σ``step`` over the window (``bench/phases.py``); a
burst ends on the host pull of its last step's tokens, so it covers their
device time.
"""

from bench.phases import step_share


def read(ctx):
    return step_share(ctx, "decode-burst")
