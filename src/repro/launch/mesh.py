"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

A FUNCTION, not a module constant — importing this module must never touch
jax device state (smoke tests see 1 device; only dryrun.py forces 512).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axes: the stack places arrays by sharding
    annotations that GSPMD propagates, not by explicit-sharding types (the
    default axis type of ``jax.make_mesh``)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    import os
    shape = (2, 16, 16) if multi_pod else (16, 16)
    override = os.environ.get("REPRO_MESH")  # e.g. "32x8" (hillclimb A/B)
    if override and not multi_pod:
        shape = tuple(int(x) for x in override.split("x"))
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 4, model: int = 2):
    """Small mesh over host CPU devices for distribution tests.

    ``data`` is a *request* — it silently clamps down to whatever the device
    count supports (the data axis only changes throughput, so any size is
    servable).  ``model`` is a *contract* — codebook row placement depends on
    it — so an unsatisfiable ``model`` raises instead of clamping.
    """
    n = len(jax.devices())
    if model > n:
        raise ValueError(
            f"make_host_mesh(model={model}) needs at least {model} devices "
            f"but only {n} are visible; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={model * data} "
            "or lower `model`")
    data = min(data, max(1, n // model))
    return make_mesh((data, model), ("data", "model"))


# TPU v5e constants for the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
ICI_LATENCY_S = 1e-6  # per-hop launch latency (order-of-magnitude v5e)


def collective_seconds(nbytes: float, participants: int,
                       kind: str = "psum") -> float:
    """First-order ring-collective time over `participants` devices.

    Per-device wire traffic of the standard ring algorithms on `nbytes` of
    payload: reduce-scatter / all-gather each move ``(p-1)/p * nbytes``;
    psum (all-reduce) is the two chained -> ``2 (p-1)/p``.  ``ppermute``
    moves the full payload one hop.  Used by
    :func:`repro.core.scheduler.op_cycles` to price ``collective`` ops on
    the ICI instead of treating cross-shard traffic as free.
    """
    p = max(int(participants), 1)
    if p == 1:
        return 0.0
    frac = {"psum": 2.0 * (p - 1) / p,
            "all_gather": (p - 1) / p,
            "reduce_scatter": (p - 1) / p,
            "ppermute": 1.0}.get(kind)
    if frac is None:
        raise ValueError(f"unknown collective kind {kind!r}")
    return ICI_LATENCY_S + frac * nbytes / ICI_BW
