"""Helpers the configuration modules share: seeds and block-wise runs."""
from __future__ import annotations

import numpy as np


def seeds(seed: int, n: int) -> list:
    """``n`` independent 31-bit seeds derived from any whole-number seed
    (the benchmark's seeds may exceed 32 bits)."""
    state = np.random.SeedSequence(abs(int(seed))).generate_state(n)
    return [int(s) & 0x7FFFFFFF for s in state]


def in_blocks(fn, arrays: list, block: int) -> list:
    """Run ``fn(*chunks)`` over fixed-size row blocks of ``arrays`` (the
    last block padded by repeating its final row, so every call has one
    shape and compiles once) and concatenate each output's valid rows."""
    n = arrays[0].shape[0]
    outs = None
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        chunk = []
        for a in arrays:
            c = a[lo:hi]
            if hi - lo < block:
                pad = np.repeat(c[-1:], block - (hi - lo), axis=0)
                c = np.concatenate([c, pad], axis=0)
            chunk.append(c)
        res = [np.asarray(r)[:hi - lo] for r in fn(*chunk)]
        outs = [[r] for r in res] if outs is None else \
            [o + [r] for o, r in zip(outs, res)]
    return [np.concatenate(o, axis=0) for o in outs]
