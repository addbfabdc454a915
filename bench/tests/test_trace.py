"""Trace reduction: busy union, idle gaps, program and kernel time, gap
labels, on hand-made events and on a trace recorded on a TPU v5e."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data"


def _trace(ops, modules=(), anchor=0.0):
    return {"device": {"/device:TPU:0": {
        tr.OPS_LINE: [list(o) for o in ops],
        tr.MODULES_LINE: [list(m) for m in modules]}},
        "anchor_ns": anchor, "window_ns": [0.0, 100.0]}


def test_union_merges_and_clips():
    iv = [(5, 10), (8, 12), (20, 25), (-5, 2), (90, 120)]
    assert tr.union(iv, 0, 100) == [(0, 2), (5, 12), (20, 25), (90, 100)]
    assert tr.busy_ns(iv, 0, 100) == 2 + 7 + 5 + 10


def test_idle_gaps_longest_first():
    gaps = tr.idle_gaps([(10, 20), (30, 35)], 0, 100)
    assert gaps == [(35, 100), (0, 10), (20, 30)]
    assert tr.idle_gaps([(0, 100)], 0, 100) == []


def test_busy_share_averages_devices():
    t = _trace([("a", 0, 50)])
    t["device"]["/device:TPU:1"] = {tr.OPS_LINE: [["b", 0, 100]]}
    assert tr.busy_share(t, 0, 100) == pytest.approx(0.75)


def test_program_and_kernel_time():
    t = _trace(ops=[("fusion.1", 0, 10), ("resonator_step_kernel", 10, 5),
                    ("resonator_step_kernel", 40, 5)],
               modules=[("jit_run_sweeps(3)", 0, 20),
                        ("jit_run_sweeps(3)", 40, 20),
                        ("jit_refill_many(4)", 70, 5)])
    p = "/device:TPU:0"
    assert tr.program_ns(t, p, "jit_run_sweeps", 0, 100) == (40.0, 2)
    assert tr.program_ns(t, p, "jit_run_sweeps", 0, 50) == (30.0, 2)
    assert tr.kernel_ns(t, p, "resonator_step", 0, 100) == (10.0, 2)
    top = tr.top_ops(t, 0, 100)
    assert top[0] == ["fusion.1", 10e-9]


def test_label_gaps_innermost_span_wins():
    spans = [("runtime", "dispatch", 0.0, 1.0),
             ("nvsa", "step", 0.1, 0.9), ("nvsa", "retire", 0.5, 0.8)]
    gaps = [(600.0, 700.0), (950.0, 990.0), (2000.0, 2100.0)]
    # host time t maps to t * 1000 ns on the trace clock
    labels = tr.label_gaps(gaps, spans, lambda t: t * 1000.0)
    assert [g[0] for g in labels] == ["nvsa/retire", "runtime/dispatch",
                                      "unattributed"]
    assert labels[0][1] == pytest.approx(100e-9)


def test_recorded_trace():
    """A window of a traced run of lvrf.poisson.noisy on one TPU v5e chip
    (ops and modules of the device plane, cut to a few milliseconds)."""
    with gzip.open(DATA / "lvrf_trace.json.gz", "rt") as f:
        t = json.load(f)
    planes = tr.device_planes(t)
    assert planes == ["/device:TPU:0"]
    lo, hi = t["window_ns"]
    share = tr.busy_share(t, lo, hi)
    assert 0.0 < share < 1.0
    ns, n = tr.program_ns(t, planes[0], "jit_run_sweeps", lo, hi)
    assert n > 0 and ns > 0
    kns, calls = tr.kernel_ns(t, planes[0], "resonator_step", lo, hi)
    # one fused-kernel call inside each one-sweep burst program
    assert (n, calls) == (3, 3) and 0 < kns <= ns
    gaps = tr.idle_gaps([(s, e) for _, s, e in tr.op_events(t, planes[0])],
                        lo, hi)
    assert sum(b - a for a, b in gaps) == pytest.approx(
        (1 - share) * (hi - lo), rel=1e-9)
