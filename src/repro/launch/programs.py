"""Compiled programs: the persistent compile cache and structural checks.

:func:`enable_compile_cache` is what every entry point calls from its
``main()`` (never at import).  The structural checks let tests and the chip
smoke assert program structure rather than timings: how many collectives or
kernel calls a traced step holds, and whether a compiled program really
contains a Pallas kernel (``tpu_custom_call``) instead of an interpret-mode
emulation.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax.extend import core as jex_core

# Fixed, git-ignored, inside the checkout: the cache key includes nothing
# of the path, but a directory that moved between runs never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_JAXPRS = (jex_core.Jaxpr, jex_core.ClosedJaxpr)


def primitive_names(jaxpr) -> list:
    """Every primitive name in ``jaxpr`` (a ``ClosedJaxpr`` or ``Jaxpr``),
    in program order, descending into the sub-jaxprs held in equation
    params (scan/while/cond bodies, jit, shard_map, Pallas kernels)."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for sub in jax.tree.leaves(list(eqn.params.values()),
                                   is_leaf=lambda x: isinstance(x, _JAXPRS)):
            if isinstance(sub, _JAXPRS):
                out += primitive_names(sub)
    return out


def has_tpu_kernel(compiled) -> bool:
    """Whether a compiled program holds a natively lowered Pallas TPU kernel
    (interpret mode lowers to plain HLO and has none)."""
    return "tpu_custom_call" in compiled.as_text()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and stands
    as it is; otherwise the cache lives at :data:`DEFAULT_CACHE_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
