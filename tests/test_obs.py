"""Observability layer (repro.obs): span trees, metrics, Chrome export,
and the zero-overhead-when-disabled contract.

Three layers of assertion, mirroring the module's three rules:

  * **span/metric semantics** on fake clocks — nesting parents correctly on
    one track, explicit parentage survives, durations never go negative,
    ``validate`` catches malformed trees, metric snapshots never reset;
  * **trace schema** — ``to_chrome_trace`` emits loadable Trace Event
    Format JSON (the contract a Perfetto user depends on);
  * **the NULL path is a behavioral no-op** — serving the SAME workload
    with tracing on and off dispatches the same device programs the same
    number of times and returns bit-equal results, and the lowered sweep
    program is byte-identical (recording never reaches inside jit).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, obs
from repro.models import lvrf
from repro.runtime.telemetry import EngineTelemetry


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# Span store semantics
# ---------------------------------------------------------------------------

def test_span_nesting_parents_on_same_track():
    clk = FakeClock()
    rec = obs.Recorder(clock=clk)
    with rec.span("outer", track="a") as outer:
        clk.tick(1.0)
        with rec.span("inner", track="a") as inner:
            clk.tick(0.5)
            inner.args["k"] = 1
        clk.tick(0.25)
    spans = rec.spans.snapshot()
    by = {s.name: s for s in spans}
    assert by["inner"].parent == by["outer"].sid
    assert by["outer"].parent is None
    assert by["outer"].t0 <= by["inner"].t0
    assert by["inner"].t1 <= by["outer"].t1
    assert by["inner"].args == {"k": 1}
    assert outer.duration == pytest.approx(1.75)
    assert obs.validate(spans) == []


def test_span_tracks_are_independent_stacks():
    clk = FakeClock()
    rec = obs.Recorder(clock=clk)
    with rec.span("a-outer", track="a"):
        with rec.span("b-top", track="b"):
            clk.tick(0.1)
    by = {s.name: s for s in rec.spans.snapshot()}
    assert by["b-top"].parent is None  # other track's stack doesn't parent


def test_begin_end_explicit_parent_and_instants():
    clk = FakeClock()
    rec = obs.Recorder(clock=clk)
    sid = rec.begin("cycle", track="sup", args={"n": 1})
    clk.tick(0.2)
    rec.instant("mark", track="sup", parent=sid)
    clk.tick(0.2)
    rec.end(sid, args={"outcome": "ok"})
    rec.end(None)  # NULL-style sid must be a silent no-op
    spans = rec.spans.snapshot()
    cyc = next(s for s in spans if s.name == "cycle")
    mark = next(s for s in spans if s.name == "mark")
    assert mark.instant and mark.parent == cyc.sid
    assert cyc.args == {"n": 1, "outcome": "ok"}
    assert cyc.duration == pytest.approx(0.4)
    assert obs.validate(spans) == []


def test_end_clamps_backwards_clock():
    clk = FakeClock()
    rec = obs.Recorder(clock=clk)
    sid = rec.begin("s", track="t")
    clk.t -= 5.0  # a hostile clock must not produce negative durations
    rec.end(sid)
    sp = rec.spans.get(sid)
    assert sp.duration == 0.0
    assert obs.validate(rec.spans.snapshot()) == []


def test_validate_flags_malformed_trees():
    clk = FakeClock()
    rec = obs.Recorder(clock=clk)
    sid = rec.begin("parent", track="t")
    clk.tick(1.0)
    rec.end(sid)
    child = rec.begin("child", track="t", parent=sid)  # starts after parent
    clk.tick(1.0)                                      # closed -> ends after
    rec.end(child)
    orphan = rec.begin("orphan", track="t", parent=10_000)
    rec.end(orphan)
    errs = obs.validate(rec.spans.snapshot())
    assert any("unknown parent" in e for e in errs)
    assert any("after" in e for e in errs)


def test_unbalanced_context_exit_unwinds_stack():
    rec = obs.Recorder(clock=FakeClock())
    with pytest.raises(RuntimeError):
        with rec.span("outer", track="a"):
            with rec.span("inner", track="a"):
                raise RuntimeError("boom")
    # both spans closed despite the exception; a fresh span parents cleanly
    with rec.span("next", track="a"):
        pass
    by = {s.name: s for s in rec.spans.snapshot()}
    assert by["next"].parent is None
    assert all(not s.open for s in rec.spans.snapshot())


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_metrics_counters_gauges_labels_snapshot_nondestructive():
    m = obs.MetricsRegistry()
    m.counter("reqs", engine="a").add(2)
    m.counter("reqs", engine="a").add(1)
    m.counter("reqs", engine="b").add(5)
    m.gauge("slots", engine="a").set(16)
    s1 = m.snapshot()
    s2 = m.snapshot()  # non-destructive: identical back-to-back reads
    assert s1 == s2
    assert s1["reqs"] == {"engine=a": 3, "engine=b": 5}
    assert s1["slots"] == {"engine=a": 16}


def test_metrics_kind_mismatch_raises():
    m = obs.MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")


def test_histogram_summary_percentiles():
    m = obs.MetricsRegistry()
    h = m.histogram("lat")
    for v in [0.001, 0.002, 0.004, 0.008, 0.1]:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5
    # percentiles interpolate within log buckets: monotone, and bounded by
    # one bucket edge (10^(1/4) with 4 buckets/decade) above the true max
    assert s["min"] <= s["p50"] <= s["p99"] <= s["max"] * 10 ** 0.25
    assert s["mean"] == pytest.approx(np.mean([0.001, 0.002, 0.004,
                                               0.008, 0.1]))


# ---------------------------------------------------------------------------
# maybe_obs env seam + NULL recorder
# ---------------------------------------------------------------------------

def test_maybe_obs_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    assert obs.maybe_obs(None) is obs.NULL
    rec = obs.Recorder()
    assert obs.maybe_obs(rec) is rec
    monkeypatch.setenv("REPRO_OBS", "1")
    auto = obs.maybe_obs(None)
    assert isinstance(auto, obs.Recorder) and auto.enabled


def test_null_recorder_is_free_and_inert():
    n = obs.NULL
    assert not n.enabled
    # ONE shared context-manager singleton: the whole disabled span cost
    assert n.span("x") is n.span("y", track="z")
    with n.span("x") as sp:
        assert sp is None
    assert n.begin("a", track="t") is None
    n.end(None)
    n.instant("i", track="t")
    n.count("c")
    n.gauge("g", 1)
    n.observe("h", 0.5)
    assert isinstance(n.now(), float)


# ---------------------------------------------------------------------------
# Engine integration: zero overhead, bit-equality, identical programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lvrf_setup():
    spec = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0))
    cfg = lvrf.LVRFConfig()
    atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(0))[0], cfg)
    rng = np.random.default_rng(7)
    vals = jnp.asarray(rng.integers(0, cfg.n_values, (4, 3)))
    queries = lvrf.encode_row(atoms, vals, cfg)
    keys = jax.random.split(jax.random.PRNGKey(42), 4)
    return spec, queries, keys


def _count_dispatches(eng) -> dict:
    """Wrap the engine's three device programs with call counters."""
    counts = {"sweeps": 0, "refill": 0, "decode": 0}
    sweeps, refill, decode = eng._sweeps, eng._refill_many, eng._decode

    def w(tag, fn):
        def wrapped(*a, **k):
            counts[tag] += 1
            return fn(*a, **k)
        return wrapped

    eng._sweeps = w("sweeps", sweeps)
    eng._refill_many = w("refill", refill)
    eng._decode = w("decode", decode)
    return counts


def _serve(eng, queries, keys):
    for i in range(queries.shape[0]):
        eng.submit(queries[i], keys=keys[i][None])
    return eng.drain()


def test_tracing_is_zero_overhead_bit_equal(lvrf_setup, monkeypatch):
    """The acceptance bar: with a live Recorder vs the NULL default, the
    same workload dispatches the same programs the same number of times and
    every result is bit-equal — recording stays outside jit, and the NULL
    path never calls the profiler."""
    spec, queries, keys = lvrf_setup
    rec = obs.Recorder()
    eng_on = engine.Engine(spec, slots=2, sweeps_per_step=2, obs=rec)
    eng_off = engine.Engine(spec, slots=2, sweeps_per_step=2)
    assert eng_on.obs is rec and eng_off.obs is obs.NULL
    # the compiled sweep program is identical with tracing on or off
    low = [e._sweeps.lower(e.qs, e.state, jnp.int32(2)).as_text()
           for e in (eng_on, eng_off)]
    assert low[0] == low[1]
    c_on, c_off = _count_dispatches(eng_on), _count_dispatches(eng_off)
    done_on = _serve(eng_on, queries, keys)

    def no_profiler(*a, **k):
        raise AssertionError("the NULL path called the profiler")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_profiler)
    done_off = _serve(eng_off, queries, keys)
    assert c_on == c_off  # identical dispatch counts
    assert len(done_on) == len(done_off) == queries.shape[0]
    for a, b in zip(done_on, done_off):
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), a.factorization, b.factorization)
    # and the traced run actually recorded the serving structure
    names = {s.name for s in rec.spans.snapshot()}
    assert {"step", "fill", "sweep-burst", "retire", "decode",
            "postprocess"} <= names
    spans = rec.spans.snapshot()
    assert eng_on.snapshot()["completed"] == queries.shape[0]
    step_sweeps = sum(s.args["sweeps"] for s in spans if s.name == "step"
                      and "sweeps" in s.args)
    assert step_sweeps == eng_on.sweeps_total >= 1
    assert obs.validate(spans) == []


def test_engine_snapshot_nondestructive_stats_drains(lvrf_setup):
    spec, queries, keys = lvrf_setup
    eng = engine.Engine(spec, slots=2, sweeps_per_step=2)
    _serve(eng, queries, keys)
    s1 = eng.snapshot()
    s2 = eng.snapshot()  # two readers see the same rolling window
    assert s1 == s2
    assert s1["engine_kind"] == "factorizer"
    assert s1["units_total"] == s1["sweeps_total"] > 0
    assert s1["window_completed"] == queries.shape[0]
    assert s1["latency_p50_ms"] is not None
    drained = eng.stats()  # read-and-reset semantics preserved
    assert drained["window_completed"] == queries.shape[0]
    assert eng.snapshot()["window_completed"] == 0
    assert eng.snapshot()["completed"] == queries.shape[0]  # totals persist


def test_engine_adopts_recorder_clock(lvrf_setup):
    spec, _, _ = lvrf_setup
    clk = FakeClock()
    rec = obs.Recorder(clock=clk)
    eng = engine.Engine(spec, slots=2, obs=rec)
    assert eng._clock is clk
    explicit = lambda: 0.0
    eng2 = engine.Engine(spec, slots=2, obs=rec, clock=explicit)
    assert eng2._clock is explicit  # an explicit clock is never overridden
    eng2.bind_obs(rec)
    assert eng2._clock is explicit


# ---------------------------------------------------------------------------
# Chrome trace export schema
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_loads():
    clk = FakeClock()
    rec = obs.Recorder(clock=clk)
    with rec.span("step", track="eng", cat="engine"):
        clk.tick(0.25)
    rec.instant("mark", track="sup")
    open_sid = rec.begin("open", track="sup")
    rec.count("reqs", 3, engine="eng")
    trace = json.loads(json.dumps(rec.to_chrome_trace(), default=str))
    evs = trace["traceEvents"]
    tracks = {e["args"]["name"] for e in evs if e["name"] == "thread_name"}
    assert tracks == {"eng", "sup"}
    x = next(e for e in evs if e.get("ph") == "X")
    assert x["name"] == "step" and x["dur"] == pytest.approx(0.25e6)
    assert x["ts"] >= 0 and x["cat"] == "engine"
    i = next(e for e in evs if e.get("ph") == "i")
    assert i["name"] == "mark" and i["s"] == "t"
    b = next(e for e in evs if e.get("ph") == "B")  # still-open span exports
    assert b["name"] == "open" and b["args"]["_span_id"] == open_sid
    assert all(("pid" in e and "tid" in e and "name" in e) for e in evs)
    assert trace["otherData"]["metrics"]["reqs"] == {"engine=eng": 3}


def test_write_chrome_trace_roundtrip(tmp_path):
    rec = obs.Recorder(clock=FakeClock())
    with rec.span("s", track="t"):
        pass
    path = tmp_path / "trace.json"
    rec.write_chrome_trace(str(path))
    loaded = json.loads(path.read_text())
    assert any(e["name"] == "s" for e in loaded["traceEvents"])


# ---------------------------------------------------------------------------
# Planner drift telemetry
# ---------------------------------------------------------------------------

def test_plan_drift_ratio():
    t = EngineTelemetry()
    assert t.plan_drift_ratio() is None
    t.on_step(0.5, 2, step_s=0.4, units=2, modeled_unit_s=0.1)
    # measured 0.2 s/unit vs modeled 0.1 s/unit -> plan is 2x optimistic
    assert t.plan_drift_ratio() == pytest.approx(2.0)
    snap = t.snapshot(now=1.0)
    assert snap["plan_drift_ratio"] == pytest.approx(2.0)
    assert snap["modeled_unit_s"] == pytest.approx(0.1)
    t.on_step(0.5, 2, step_s=0.0, units=0)  # idle step: drift unchanged
    assert t.plan_drift_ratio() == pytest.approx(2.0)
