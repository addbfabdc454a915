"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace is first turned into a plain dictionary (``load_xplane``), which is
also the format of the recorded trace the tests use::

    {"device": {"/device:TPU:0": {"XLA Ops": [[name, start_ns, dur_ns], ...],
                                  "XLA Modules": [...]}},
     "anchor_ns": 1234.0,          # the harness's anchor annotation
     "window_ns": [0.0, 2.5e9]}    # the traced window, trace clock

Times are nanoseconds on the trace's own clock.  Every reduction below is
a sum over the traced window: busy time is the union of the intervals in
which an operation ran on a device, idle gaps are the holes in that union,
and a program's or kernel's device time is the sum of its events.
"""
from __future__ import annotations

import glob
import os
import re

ANCHOR = "bench_anchor"
# The line of a device plane that holds one event per executed operation;
# the module line holds one event per program execution.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(log_dir: str) -> dict:
    """Read the newest ``*.xplane.pb`` under ``log_dir`` into the plain
    dictionary described in the module docstring."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"device": {}, "anchor_ns": None, "window_ns": None}
    lo, hi = None, None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                evs = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                       for ev in line.events]
                lines[line.name] = evs
            out["device"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    t0 = float(ev.start_ns)
                    t1 = t0 + float(ev.duration_ns)
                    lo = t0 if lo is None else min(lo, t0)
                    hi = t1 if hi is None else max(hi, t1)
                    if ev.name == ANCHOR and out["anchor_ns"] is None:
                        out["anchor_ns"] = t0
    out["window_ns"] = [lo if lo is not None else 0.0,
                        hi if hi is not None else 0.0]
    return out


def device_planes(trace: dict) -> list:
    """Names of the accelerator planes, in device order."""
    return sorted(p for p in trace["device"] if "TPU" in p or "GPU" in p)


def op_events(trace: dict, plane: str) -> list:
    """``(name, start_ns, end_ns)`` of every operation on one device."""
    lines = trace["device"].get(plane, {})
    evs = lines.get(OPS_LINE)
    if evs is None:  # a device plane without an ops line: take every line
        evs = [e for ln in lines.values() for e in ln]
    return [(n, s, s + d) for n, s, d in evs]


def module_events(trace: dict, plane: str) -> list:
    """``(name, start_ns, end_ns)`` of every program execution on one
    device (empty when the plane has no module line)."""
    evs = trace["device"].get(plane, {}).get(MODULES_LINE, [])
    return [(n, s, s + d) for n, s, d in evs]


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    clipped = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                     if b > lo and a < hi)
    merged = []
    for a, b in clipped:
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def busy_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(b - a for a, b in union(intervals, lo, hi))


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """The holes of the union of ``intervals`` inside ``[lo, hi]``, as
    ``(start, end)``, longest first."""
    gaps, t = [], lo
    for a, b in union(intervals, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def busy_share(trace: dict, lo: float, hi: float) -> float:
    """Device busy time over ``[lo, hi]``, averaged over the devices."""
    planes = device_planes(trace)
    if not planes or hi <= lo:
        return 0.0
    busy = [busy_ns([(s, e) for _, s, e in op_events(trace, p)], lo, hi)
            for p in planes]
    return sum(busy) / len(busy) / (hi - lo)


def program_ns(trace: dict, plane: str, prefix: str, lo: float,
               hi: float) -> tuple:
    """``(device ns, executions)`` of the programs whose module name starts
    with ``prefix`` (``jit_run_sweeps`` matches ``jit_run_sweeps(17)``),
    counting the part of each execution inside ``[lo, hi]``."""
    total, count = 0.0, 0
    for name, s, e in module_events(trace, plane):
        if name.startswith(prefix) and e > lo and s < hi:
            total += min(e, hi) - max(s, lo)
            count += 1
    return total, count


def kernel_ns(trace: dict, plane: str, needle: str, lo: float,
              hi: float) -> tuple:
    """``(device ns, calls)`` of the operations whose own name (not their
    operands) contains ``needle`` (a Pallas kernel's name), inside
    ``[lo, hi]``."""
    total, count = 0.0, 0
    for name, s, e in op_events(trace, plane):
        if needle in name.split(" = ", 1)[0] and e > lo and s < hi:
            total += min(e, hi) - max(s, lo)
            count += 1
    return total, count


_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(name: str) -> str:
    """``%fusion.3 fusion`` for an event named by its HLO instruction text
    (``%fusion.3 = f32[...] fusion(...), ...``); a custom call adds its
    target.  Other names pass unchanged."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    m = _OPCODE.search(rhs)
    label = f"{lhs} {m.group(1)}" if m else lhs
    t = _TARGET.search(rhs)
    return f"{label} {t.group(1)}" if t else label


def top_ops(trace: dict, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` operations (by ``op_label``) with the most device time
    inside ``[lo, hi]``, summed over devices: ``[[label, seconds], ...]``."""
    tot: dict = {}
    for plane in device_planes(trace):
        for name, s, e in op_events(trace, plane):
            if e > lo and s < hi:
                key = op_label(name)
                tot[key] = tot.get(key, 0.0) + (min(e, hi) - max(s, lo))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in ranked]


def label_gaps(gaps, spans, to_trace_ns, k: int = 10) -> list:
    """Name each of the ``k`` longest idle gaps by the host span that
    covers its midpoint: the innermost one (latest start) wins, and a gap
    no span covers is ``unattributed``.

    ``spans`` are ``(track, name, t0, t1)`` on the host clock;
    ``to_trace_ns`` maps a host time to the trace clock.  Returns
    ``[[label, seconds], ...]``.
    """
    mapped = [(f"{track}/{name}", to_trace_ns(t0), to_trace_ns(t1))
              for track, name, t0, t1 in spans]
    out = []
    for a, b in gaps[:k]:
        mid = 0.5 * (a + b)
        best = None
        for label, s, e in mapped:
            if s <= mid <= e and (best is None or s > best[1]):
                best = (label, s)
        out.append([best[0] if best else "unattributed", (b - a) * 1e-9])
    return out
