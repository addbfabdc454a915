"""Jit'd public wrappers around the circconv Pallas kernel.

Mirrors CogSys's ST-mapping rule (Sec. V-D): pick the execution scheme from
the workload shape (k convolutions of length L) and the platform.  On
non-TPU backends the kernel runs in interpret mode (correctness path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.circconv import kernel as _k
from repro.kernels.circconv import ref as _ref


def block_circconv(xb: jax.Array, yb: jax.Array) -> jax.Array:
    """Block-wise circular convolution, blocked layout [..., B, L] -> [..., B, L].

    ST-mapping analogue: many independent rows -> row-parallel VPU kernel
    ("temporal mapping", CWP over rows); a single long row -> circulant-tile
    MXU kernel ("spatial mapping", folds over output tiles).
    """
    lead = xb.shape[:-1]
    L = xb.shape[-1]
    x2 = xb.reshape(-1, L)
    y2 = jnp.broadcast_to(yb, xb.shape).reshape(-1, L)
    n_rows = x2.shape[0]
    if n_rows == 1 and L >= 512:
        out = _k.circconv_single_mxu(x2[0], y2[0],
                                     interpret=resolve_interpret())[None]
    else:
        out = _k.circconv_rows(x2, y2, interpret=resolve_interpret())
    return out.reshape(*lead, L)


def block_circcorr(qb: jax.Array, yb: jax.Array) -> jax.Array:
    """Block-wise circular correlation (unbinding direction)."""
    inv = jnp.concatenate([yb[..., :1], yb[..., 1:][..., ::-1]], axis=-1)
    return block_circconv(qb, inv)


# Re-export the oracle for tests/benchmarks.
block_circconv_ref = _ref.block_circconv_ref
circconv_rows_ref = _ref.circconv_rows_ref
