"""The engine-phase readers (fill, decode and postprocess shares, slot
occupancy) on hand-made spans with args."""
import types

import pytest

from bench import harness


def _span(track, name, t0, t1, **args):
    return types.SimpleNamespace(track=track, name=name, t0=t0, t1=t1,
                                 instant=False, args=args)


SPANS = [
    # step 1: [0.0, 0.4]
    _span("cell", "step", 0.0, 0.4, sweeps=2),
    _span("cell", "fill", 0.0, 0.1, rows=3),
    _span("cell", "sweep-burst", 0.1, 0.2, live=3, slots=8, sweeps=2),
    _span("cell", "retire", 0.2, 0.25),
    _span("cell", "decode", 0.25, 0.3),
    _span("cell", "postprocess", 0.3, 0.4, queries=2),
    # step 2: [0.5, 0.9]
    _span("cell", "step", 0.5, 0.9, sweeps=4),
    _span("cell", "fill", 0.5, 0.55, rows=1),
    _span("cell", "sweep-burst", 0.55, 0.75, live=4, slots=8, sweeps=4),
    _span("cell", "retire", 0.75, 0.8),
    _span("cell", "decode", 0.8, 0.85),
    _span("cell", "postprocess", 0.85, 0.9, queries=1),
    # another engine's step and the stepper's idle wait do not count
    _span("other", "step", 0.0, 0.9),
    _span("other", "fill", 0.0, 0.9),
    _span("runtime", "idle", 0.4, 0.5),
]


def _ctx(**kw):
    base = dict(spans=SPANS, engine="cell", host0=0.0, host1=1.0)
    base.update(kw)
    return types.SimpleNamespace(**base)


def _read(base, ctx):
    mod = harness.load_module(harness.BENCH_DIR / "metrics" / f"{base}.py",
                              f"bench_test_metric_{base}")
    return mod.read(ctx)


# (reader, whole window, window [0.35, 0.6] cutting both steps)
CASES = [
    # fills 0.1 + 0.05 over steps 0.8; cut: 0.05 of fill over 0.05 + 0.1
    ("fill_share", 100 * 0.15 / 0.8, 100 * 0.05 / 0.15),
    # decodes 0.05 + 0.05; cut: none inside -> the reader has nothing
    ("decode_share", 100 * 0.1 / 0.8, None),
    # postprocess 0.1 + 0.05; cut: 0.05 of step 1's
    ("postprocess_share", 100 * 0.15 / 0.8, 100 * 0.05 / 0.15),
    # bursts (3 x 2 + 4 x 4) / (8 x 2 + 8 x 4); cut: only step 2's burst
    # starts inside
    ("slot_occupancy", 100 * 22 / 48, 100 * 16 / 32),
]


@pytest.mark.parametrize("base,whole,cut", CASES, ids=[c[0] for c in CASES])
def test_phase_reader(base, whole, cut):
    assert _read(base, _ctx()) == pytest.approx(whole)
    inner = _read(base, _ctx(host0=0.35, host1=0.6))
    assert inner == (None if cut is None else pytest.approx(cut))
    # nothing to read: another engine's name, no spans, or spans without
    # the phase (a program that records no such span or arg)
    assert _read(base, _ctx(engine="absent")) is None
    assert _read(base, _ctx(spans=[])) is None
    bare = [_span(s.track, s.name, s.t0, s.t1) for s in SPANS
            if s.name in ("step", "sweep-burst")]
    assert _read(base, _ctx(spans=bare)) is None
