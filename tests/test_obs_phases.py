"""Engine step phases, the stepper's idle span, and spans mirrored into the
profiler's trace.

  * a traced ``Engine.step`` records ``fill`` / ``sweep-burst`` /
    ``retire`` / ``decode`` / ``postprocess`` as non-overlapping siblings
    under ``step``, with the args the benchmark's readers take (``rows``,
    ``live``/``slots``/``sweeps``, ``queries``);
  * a traced ``Runtime`` records one closed ``idle`` span per stretch of
    stepper passes with nothing to do;
  * every stack-scoped span of a ``Recorder`` is a ``<track>/<name>`` host
    event in a ``jax.profiler`` trace, at the time the span records, once
    the trace is tied to the host clock by an anchor annotation.
"""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, obs
from repro import runtime as rt
from repro.models import lvrf, nvsa

PHASES = ("fill", "sweep-burst", "retire", "decode", "postprocess")
RESULT_TIMEOUT_S = 300.0
ANCHOR = "bench_anchor"  # the anchor annotation's name in bench/trace.py


def _lvrf_requests(n: int, seed: int):
    spec = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0))
    cfg = lvrf.LVRFConfig()
    atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(0))[0], cfg)
    vals = np.random.default_rng(seed).integers(0, cfg.n_values, (n, 3))
    rows = lvrf.encode_row(atoms, jnp.asarray(vals), cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    return spec, [{"queries": rows[i], "keys": keys[i][None]}
                  for i in range(n)]


def _nvsa_requests(n: int, seed: int):
    cfg = nvsa.NVSAConfig()
    spec = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(0),
                                 cfg=cfg)
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        attrs = jnp.asarray(rng.integers(0, (5, 6, 10), (8, 3)))
        ctx = nvsa.target_query(spec.codebooks, attrs, cfg)
        out.append({"queries": ctx,
                    "keys": jax.random.split(jax.random.PRNGKey(seed + i), 8),
                    "meta": {"cand": ctx}})
    return spec, out


@pytest.fixture(scope="module", params=["nvsa", "lvrf"])
def workload(request):
    if request.param == "nvsa":
        return _nvsa_requests(2, seed=5)
    return _lvrf_requests(5, seed=7)


def test_step_phases_are_siblings_under_step(workload):
    spec, reqs = workload
    rec = obs.Recorder()
    eng = engine.Engine(spec, slots=4, sweeps_per_step=2, obs=rec)
    for kw in reqs:
        eng.submit(**kw)
    done = eng.drain()
    assert len(done) == len(reqs)
    spans = rec.spans.snapshot()
    assert obs.validate(spans) == []
    steps = [s for s in spans if s.name == "step"]
    assert steps
    kids_of = {st.sid: [] for st in steps}
    for s in spans:
        if s.parent in kids_of:
            kids_of[s.parent].append(s)
    phase_ids = {s.sid for s in spans if s.name in PHASES}
    assert all(s.parent in kids_of for s in spans if s.sid in phase_ids)
    assert not any(s.parent in phase_ids for s in spans)  # nothing nests
    for st in steps:
        kids = sorted(kids_of[st.sid], key=lambda s: s.t0)
        assert {k.name for k in kids} <= set(PHASES)
        for a, b in zip(kids, kids[1:]):  # siblings never overlap
            assert a.t1 <= b.t0, (a.name, b.name)
        post = [k for k in kids if k.name == "postprocess"]
        assert len(post) == st.args["retired"]
        if post:
            assert set(PHASES) <= {k.name for k in kids}
    bursts = [s for s in spans if s.name == "sweep-burst"]
    assert all(1 <= b.args["live"] <= b.args["slots"] == eng.slots
               and b.args["sweeps"] >= 1 for b in bursts)
    rows = sum(s.args["rows"] for s in spans if s.name == "fill")
    assert rows == sum(r.num_queries for r in done)
    queries = sorted(s.args["queries"] for s in spans
                     if s.name == "postprocess")
    assert queries == sorted(r.num_queries for r in done)


def test_runtime_records_one_idle_span_per_idle_stretch():
    spec, reqs = _lvrf_requests(2, seed=11)
    rec = obs.Recorder()
    r = rt.Runtime(obs=rec)
    r.register("lvrf", engine.Engine(spec, slots=2, sweeps_per_step=2))
    with r:
        time.sleep(0.05)  # stretch 1: nothing submitted yet
        gids = [r.submit("lvrf", kw["queries"], keys=kw["keys"])
                for kw in reqs]
        for g in gids:
            r.result(g, timeout=RESULT_TIMEOUT_S)
        time.sleep(0.05)  # stretch 2: everything answered
    spans = rec.spans.snapshot()
    assert obs.validate(spans) == []
    idle = sorted((s for s in spans if s.name == "idle"), key=lambda s: s.t0)
    assert len(idle) == 2
    assert all(s.track == "runtime" and not s.open for s in idle)
    work = [s for s in spans if s.track == "runtime"
            and s.name in ("ingest", "dispatch")]
    assert work
    assert idle[0].t1 <= min(s.t0 for s in work)
    assert idle[1].t0 >= max(s.t1 for s in work)


def test_null_runtime_never_calls_profiler(monkeypatch):
    spec, reqs = _lvrf_requests(1, seed=13)

    def no_profiler(*a, **k):
        raise AssertionError("the NULL path called the profiler")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_profiler)
    r = rt.Runtime()
    r.register("lvrf", engine.Engine(spec, slots=2, sweeps_per_step=2))
    with r:
        time.sleep(0.02)
        gid = r.submit("lvrf", reqs[0]["queries"], keys=reqs[0]["keys"])
        req = r.result(gid, timeout=RESULT_TIMEOUT_S)
        time.sleep(0.02)
    assert req.result is not None


def _host_events(log_dir) -> dict:
    """``{name: [start_ns, ...]}`` of the host events of the newest trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(float(ev.start_ns))
    return {k: sorted(v) for k, v in out.items()}


def test_spans_are_host_events_in_the_profiler_trace(tmp_path):
    spec, reqs = _lvrf_requests(4, seed=17)
    rec = obs.Recorder()
    eng = engine.Engine(spec, slots=2, sweeps_per_step=2)
    eng.bind_obs(rec, track="lvrf")
    eng.submit(**reqs[0])  # compile outside the trace
    eng.drain()
    n0 = len(rec.spans)
    jax.profiler.start_trace(str(tmp_path))
    try:
        host0 = rec.clock()
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass
        for kw in reqs[1:]:
            eng.submit(**kw)
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    spans = rec.spans.snapshot()[n0:]
    events = _host_events(tmp_path)
    anchor = events[ANCHOR][0]
    for name in ("step", "sweep-burst"):
        starts = [anchor + (s.t0 - host0) * 1e9 for s in spans
                  if s.name == name]
        traced = events.get(f"lvrf/{name}", [])
        assert len(traced) == len(starts) >= 1, name
        for want, got in zip(sorted(starts), traced):
            assert abs(want - got) < 1e6, (name, want - got)
