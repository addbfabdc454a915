"""Pallas kernels for the paper's compute hot spots (kernel + ops + ref each).

:func:`resolve_interpret` is the one place that decides whether a kernel
runs compiled or in Pallas interpret mode.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``None`` compiles on a TPU and interprets on every other backend (the
    CPU test path); a bool forces the choice."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
