"""The ``fill_row_ms`` reader on the engine-phase readers' hand-made
spans."""
import pytest

from bench.tests.test_phase_metrics import SPANS, _ctx, _read, _span


def test_fill_row_ms_whole_and_cut_window():
    # fills 0.1 s (3 rows) and 0.05 s (1 row); the other engine's fill and
    # the runtime's idle span do not count
    assert _read("fill_row_ms", _ctx()) == pytest.approx(1e3 * 0.15 / 4)
    # window [0.35, 0.6]: only step 2's fill starts inside
    assert _read("fill_row_ms", _ctx(host0=0.35, host1=0.6)) == \
        pytest.approx(1e3 * 0.05 / 1)


def test_fill_row_ms_counts_fills_without_free_slots():
    spans = SPANS + [_span("cell", "fill", 0.92, 0.94, rows=0)]
    assert _read("fill_row_ms", _ctx(spans=spans)) == \
        pytest.approx(1e3 * 0.17 / 4)


def test_fill_row_ms_none_without_rows():
    # fill spans without the `rows` arg, another engine's name, no spans,
    # and a window whose fills slotted nothing
    bare = [_span(s.track, s.name, s.t0, s.t1) for s in SPANS]
    assert _read("fill_row_ms", _ctx(spans=bare)) is None
    assert _read("fill_row_ms", _ctx(engine="absent")) is None
    assert _read("fill_row_ms", _ctx(spans=[])) is None
    empty = [_span("cell", "fill", 0.0, 0.1, rows=0)]
    assert _read("fill_row_ms", _ctx(spans=empty)) is None
