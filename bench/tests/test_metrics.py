"""Each per-layer metric reader on a hand-made context."""
import types

import pytest

from bench import harness
from bench import trace as tr


def _span(sid, track, name, t0, t1, parent=None, instant=False):
    return types.SimpleNamespace(sid=sid, track=track, name=name, t0=t0,
                                 t1=t1, parent=parent, instant=instant)


def _reader(base):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{base}.py",
                               f"bench_test_metric_{base}")


def _ctx(**kw):
    trace = {"device": {"/device:TPU:0": {
        tr.OPS_LINE: [["fusion", 0.0, 2e8], ["resonator_step", 4e8, 1e8]],
        tr.MODULES_LINE: [["jit_run_sweeps(1)", 0.0, 3e8],
                          ["jit_run_sweeps(1)", 4e8, 2e8]]}},
        "anchor_ns": 0.0, "window_ns": [0.0, 1e9]}
    spans = [
        _span(1, "requests", "request", 0.0, 0.5),
        _span(2, "requests", "admit", 0.1, 0.1, parent=1, instant=True),
        _span(3, "requests", "request", 0.2, 0.6),
        _span(4, "requests", "admit", 0.3, 0.3, parent=3, instant=True),
        _span(5, "cell", "step", 0.0, 0.4), _span(6, "cell", "sweep-burst",
                                                  0.1, 0.2),
        _span(7, "cell", "step", 0.5, 0.9), _span(8, "cell", "sweep-burst",
                                                  0.6, 0.9),
    ]
    base = dict(trace=trace, lo=0.0, hi=1e9, planes=["/device:TPU:0"],
                window_s=1.0, host0=0.0, host1=1.0, spans=spans,
                engine="cell", sweeps=10, slots=128,
                sweep_work=lambda n: (1e9 * n, 1e6 * n), row_flops=1e6,
                row_sweeps=1000,
                peaks={"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12},
                chips=1, metric=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_queue_wait_share():
    # waits 0.1 + 0.1 over walls 0.5 + 0.4
    assert _reader("queue_wait_share").read(_ctx()) == pytest.approx(
        100 * 0.2 / 0.9)
    assert _reader("queue_wait_share").read(_ctx(spans=[])) is None
    # a window that cuts both requests: waits 0 + 0.05 over 0.25 + 0.3
    inner = _ctx(host0=0.25, host1=0.55)
    assert _reader("queue_wait_share").read(inner) == pytest.approx(
        100 * 0.05 / 0.55)


def test_host_share():
    # steps 0.4 + 0.4, bursts 0.1 + 0.3
    assert _reader("host_share").read(_ctx()) == pytest.approx(50.0)
    assert _reader("host_share").read(_ctx(engine="other")) is None
    # a window inside one step: only the overlap counts
    # (step [0.5, 0.9] and its burst [0.6, 0.9], window [0.55, 0.75])
    inner = _ctx(host0=0.55, host1=0.75)
    assert _reader("host_share").read(inner) == pytest.approx(
        100 * (0.2 - 0.15) / 0.2)


def test_device_idle_share():
    # ops cover [0, 0.2] and [0.4, 0.5] of a 1 s window
    assert _reader("device_idle_share").read(_ctx()) == pytest.approx(70.0)
    assert _reader("device_idle_share").read(_ctx(planes=[])) is None


def test_mfu():
    # 1000 row-sweeps x 1e6 flops over 1 s x 1e14
    assert _reader("mfu").read(_ctx()) == pytest.approx(1e-3)
    assert _reader("mfu").read(_ctx(row_sweeps=0)) is None
    # the whole measured window counts, not the profiled part of it
    assert _reader("mfu").read(_ctx(host1=2.0, window_s=0.5)) == \
        pytest.approx(5e-4)


def test_sweep_roofline():
    # least time per sweep: max(128e9 / 1e14, 128e6 / 1e12) = 1.28 ms;
    # measured 0.5 s / 10 sweeps = 50 ms
    assert _reader("sweep_roofline").read(_ctx()) == pytest.approx(
        100 * 1.28e-3 / 0.05)
    assert _reader("sweep_roofline").read(_ctx(sweeps=0)) is None


def test_resonator_step_roofline():
    # one kernel call of 0.1 s
    assert _reader("resonator_step_roofline").read(_ctx()) == pytest.approx(
        100 * 1.28e-3 / 0.1)
    empty = _ctx()
    empty.trace["device"]["/device:TPU:0"][tr.OPS_LINE] = []
    assert _reader("resonator_step_roofline").read(empty) is None
