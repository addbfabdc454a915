"""Observability through the live runtime: one recorder, one clock, one
trace for the whole serving stack.

What tests/test_obs.py proves on bare engines, this file proves through the
threaded :class:`repro.runtime.Runtime`:

  * ``Runtime(obs=...)`` rebinds default-built engines onto the runtime's
    recorder at ``register`` (through the ChaosEngine wrapper's attribute
    forwarding), so engine spans, request spans, and supervisor spans land
    on ONE monotonic clock and export as one Chrome trace;
  * every request-lifecycle span closes — from whichever thread resolves
    the future — with the resolution outcome;
  * a chaos run tells its story: the injection instant on the engine's
    track, then a supervisor-track ``fault-cycle`` span whose child
    instants walk fault → quarantined → recovered.

Every blocking wait carries a timeout — these tests drive a background
stepper thread and must fail loudly instead of hanging CI.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, obs
from repro import runtime as rt
from repro.models import lvrf
from repro.runtime import faults as flt

RESULT_TIMEOUT_S = 300.0  # generous per-request wait; CI guards the step

FAST_FAILURE = rt.FailurePolicy(max_restarts=50, backoff_initial_s=0.01,
                                backoff_max_s=0.05, health_check_every=2)


@pytest.fixture(scope="module")
def lvrf_setup():
    spec = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0))
    cfg = lvrf.LVRFConfig()
    atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(0))[0], cfg)
    return spec, cfg, atoms


def _lvrf_queries(cfg, atoms, n_good: int, n_junk: int, seed: int):
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(rng.integers(0, cfg.n_values, (n_good, 3)))
    good = lvrf.encode_row(atoms, vals, cfg)
    junk = jnp.asarray(rng.normal(size=(n_junk, cfg.vsa.dim)), jnp.float32)
    return vals, good, junk


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def test_runtime_binds_engines_onto_one_recorder(lvrf_setup):
    """register() adopts default-built engines into the runtime's recorder
    (obs + clock + track=registered name); request spans open at submit and
    close with the outcome; the whole run exports as one Chrome trace."""
    spec, cfg, atoms = lvrf_setup
    _, good, _ = _lvrf_queries(cfg, atoms, n_good=3, n_junk=0, seed=31)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    rec = obs.Recorder()
    eng = engine.Engine(spec, slots=2, sweeps_per_step=2)
    assert eng.obs is obs.NULL  # default-built: nothing recorded yet
    r = rt.Runtime(obs=rec, failure=FAST_FAILURE)
    r.register("lvrf", eng)
    assert eng.obs is rec  # rebound at registration...
    assert eng.obs_track == "lvrf"  # ...under the registered name
    assert eng._clock is rec.clock  # ...on the recorder's clock
    assert r._clock is rec.clock  # the runtime itself steps the same clock
    with r:
        gids = [r.submit("lvrf", good[i], keys=keys[i][None])
                for i in range(3)]
        reqs = [r.result(g, timeout=RESULT_TIMEOUT_S) for g in gids]
        # non-destructive stats: two scrapes see the same rolling window
        s1, s2 = r.stats()["lvrf"], r.stats()["lvrf"]
        assert s1["window_completed"] == s2["window_completed"] == 3
        assert s1["engine_kind"] == "factorizer"
        assert "plan_drift_ratio" in s1["telemetry"]
        assert s1["telemetry"]["modeled_unit_s"] is not None
    assert all(req.result is not None for req in reqs)
    spans = rec.spans.snapshot()
    assert obs.validate(spans) == []
    by = _by_name(spans)
    # one request span per submit, all closed, all resolved ok
    reqs_spans = by["request"]
    assert len(reqs_spans) == 3
    assert all(not s.open and s.args["outcome"] == "ok" for s in reqs_spans)
    # admit instants ride as children of their request span
    req_sids = {s.sid for s in reqs_spans}
    admits = by["admit"]
    assert len(admits) == 3
    assert all(a.instant and a.parent in req_sids for a in admits)
    # engine internals landed on the engine's registered track
    assert {s.track for s in by["step"]} == {"lvrf"}
    assert {"sweep-burst", "retire"} <= set(by)
    # engine steps are framed by the request lifecycle on the shared clock
    t_open = min(s.t0 for s in reqs_spans)
    t_close = max(s.t1 for s in reqs_spans)
    assert any(t_open <= s.t0 and s.t1 <= t_close for s in by["step"])
    snap = rec.metrics.snapshot()
    # resolved counters carry the request class; unlabeled submits default
    # to the engine kind
    assert snap["resolved"] == {"class=factorizer,outcome=ok": 3}
    assert eng.snapshot()["completed"] == 3
    # planner drift is surfaced continuously as gauges, not only at retunes
    assert "plan_drift" in snap and "engine=lvrf" in snap["plan_drift"]
    assert snap["modeled_unit_s"]["engine=lvrf"] > 0
    # per-class latency histogram feeds snapshot-side quantiles
    lat = snap["request_latency_s"]["class=factorizer"]
    assert lat["count"] == 3
    assert obs.quantile(lat, 95) is not None
    # and it all exports as ONE trace: every track present, JSON-clean
    evs = rec.to_chrome_trace()["traceEvents"]
    tracks = {e["args"]["name"] for e in evs if e["name"] == "thread_name"}
    assert {"requests", "lvrf"} <= tracks


def test_chaos_run_traces_the_fault_cycle(lvrf_setup):
    """The chaos story in one trace: chaos-inject on the engine track, then
    a supervisor fault-cycle span with fault/quarantined/recovered child
    instants, the engine's recover span, and every request span closed."""
    spec, cfg, atoms = lvrf_setup
    _, good, junk = _lvrf_queries(cfg, atoms, n_good=2, n_junk=2, seed=33)
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    rec = obs.Recorder()
    inner = engine.Engine(spec, slots=2, sweeps_per_step=2)
    # seed 1 draws (0.51, 0.95, 0.14, ...) at rate 0.4: the fault fires on
    # the THIRD step — after the junk rows are live mid-trajectory, so
    # recovery has rows to replay
    chaos = flt.ChaosEngine(inner, flt.FaultPlan(
        seed=1, step_error_rate=0.4, max_faults=1))
    r = rt.Runtime(obs=rec, failure=FAST_FAILURE)
    r.register("lvrf", chaos)  # bind_obs resolves through the wrapper...
    assert inner.obs is rec  # ...onto the wrapped engine
    with r:
        # junk first: they hold the slots mid-trajectory when the fault
        # lands, so recovery has live rows to replay
        jids = [r.submit("lvrf", junk[j], keys=keys[j][None])
                for j in range(2)]
        gids = [r.submit("lvrf", good[i], keys=keys[2 + i][None])
                for i in range(2)]
        out = r.drain(timeout=RESULT_TIMEOUT_S, return_exceptions=True)
    assert len(out) == 4 and all(not isinstance(o, Exception) for o in out)
    assert chaos.injected["step_error"] == 1
    spans = rec.spans.snapshot()
    assert obs.validate(spans) == []
    by = _by_name(spans)
    # the injection is visible on the ENGINE's track, stamped by the harness
    inj = by["chaos-inject"]
    assert len(inj) == 1 and inj[0].track == "lvrf"
    assert inj[0].args["kind"] == "step_error"
    # one fault-cycle span on the supervisor track, closed by recovery
    cycles = by["fault-cycle"]
    assert len(cycles) == 1
    cyc = cycles[0]
    assert cyc.track == "supervisor" and not cyc.open
    assert cyc.args["engine"] == "lvrf"
    assert cyc.args["outcome"] == "recovered"
    # its children narrate the episode in order on the one shared clock
    kids = {s.name: s for s in spans if s.parent == cyc.sid}
    assert {"fault", "quarantined", "recovered"} <= set(kids)
    assert kids["fault"].t0 <= kids["quarantined"].t0 \
        <= kids["recovered"].t0
    assert kids["fault"].args["kind"] == "injected"
    assert kids["recovered"].args["replayed"] >= 1
    # the injection precedes the fault it causes
    assert inj[0].t0 <= kids["fault"].t0
    # the engine-side recover span landed on the engine track
    recov = by["recover"]
    assert len(recov) == 1 and recov[0].track == "lvrf"
    assert recov[0].args["replayed"] == kids["recovered"].args["replayed"]
    # every request span closed ok — the chaos invariant, in trace form
    assert all(not s.open and s.args["outcome"] == "ok"
               for s in by["request"])
    snap = rec.metrics.snapshot()
    assert snap["faults"] == {"engine=lvrf": 1}
    assert snap["quarantines"] == {"engine=lvrf": 1}
    assert snap["recoveries"] == {"engine=lvrf": 1}
    assert snap["chaos_injected"] == {"kind=step_error": 1}
    # Runtime.stats reads the chaos counters through the wrapper's snapshot
    stats = r.stats()["lvrf"]
    assert stats["chaos"]["step_error"] == 1
    assert stats["recoveries"] == 1


def test_failed_requests_close_spans_with_error(lvrf_setup):
    """A future that resolves to a structured fault still closes its
    request span — with the error type as the outcome."""
    spec, cfg, atoms = lvrf_setup
    _, good, junk = _lvrf_queries(cfg, atoms, n_good=1, n_junk=1, seed=35)
    keys = jax.random.split(jax.random.PRNGKey(17), 2)
    rec = obs.Recorder()
    r = rt.Runtime(obs=rec, failure=FAST_FAILURE)
    r.register("lvrf", engine.Engine(spec, slots=2, sweeps_per_step=2))
    with r:
        doomed = r.submit("lvrf", junk[0], keys=keys[0][None],
                          deadline_s=0.0)  # guaranteed miss
        ok = r.submit("lvrf", good[0], keys=keys[1][None])
        with pytest.raises(flt.DeadlineExceededError):
            r.result(doomed, timeout=RESULT_TIMEOUT_S)
        r.result(ok, timeout=RESULT_TIMEOUT_S)
    spans = {s.args.get("gid"): s for s in rec.spans.snapshot()
             if s.name == "request"}
    assert not spans[doomed].open
    assert spans[doomed].args["outcome"] == "DeadlineExceededError"
    assert spans[ok].args["outcome"] == "ok"
    snap = rec.metrics.snapshot()
    assert snap["resolved"] == {"class=factorizer,outcome=ok": 1,
                                "class=factorizer,outcome=error": 1}
    assert obs.validate(rec.spans.snapshot()) == []
    # the SLO tracker routed both outcomes under the default class
    slo = r.stats()["slo"]["factorizer"]
    assert slo["completed"] == 1 and slo["deadline_missed"] == 1
    assert slo["deadline_miss_rate"] == 0.5


def test_request_classes_flow_into_spans_metrics_and_slo(lvrf_setup):
    """submit(class_=...) labels the request span, the resolved counter,
    the latency histogram, and the per-class SLO snapshot; unlabeled
    requests default to the engine kind."""
    spec, cfg, atoms = lvrf_setup
    _, good, _ = _lvrf_queries(cfg, atoms, n_good=3, n_junk=0, seed=41)
    keys = jax.random.split(jax.random.PRNGKey(19), 3)
    rec = obs.Recorder()
    r = rt.Runtime(obs=rec, failure=FAST_FAILURE,
                   slo={"interactive": obs.SLOTarget(30.0, percentile=95)})
    r.register("lvrf", engine.Engine(spec, slots=2, sweeps_per_step=2))
    with r:
        a = r.submit("lvrf", good[0], keys=keys[0][None],
                     class_="interactive")
        b = r.submit("lvrf", good[1], keys=keys[1][None],
                     class_="interactive")
        c = r.submit("lvrf", good[2], keys=keys[2][None])  # default class
        for g in (a, b, c):
            r.result(g, timeout=RESULT_TIMEOUT_S)
        slo = r.stats()["slo"]
    assert set(slo) == {"interactive", "factorizer"}
    assert slo["interactive"]["submitted"] == 2
    assert slo["interactive"]["completed"] == 2
    assert slo["interactive"]["latency_p95_s"] > 0
    # the generous target is attained on a healthy run
    assert slo["interactive"]["attainment"] == 1.0
    assert slo["interactive"]["attained"] is True
    # untargeted default class still reports percentiles, no attainment
    assert slo["factorizer"]["completed"] == 1
    assert slo["factorizer"]["attainment"] is None
    spans = {s.args["gid"]: s for s in rec.spans.snapshot()
             if s.name == "request"}
    assert spans[a].args["class"] == "interactive"
    assert spans[c].args["class"] == "factorizer"
    snap = rec.metrics.snapshot()
    assert snap["resolved"] == {"class=interactive,outcome=ok": 2,
                                "class=factorizer,outcome=ok": 1}
    assert snap["request_latency_s"]["class=interactive"]["count"] == 2


def test_class_labels_are_free_under_null_recorder(lvrf_setup):
    """Zero-overhead contract extended to the class-label path: with the
    NULL recorder, submitting with class_ labels records nothing, the SLO
    tracker still counts (host arithmetic, like telemetry), and results
    are bit-equal to an untraced, unlabeled run."""
    spec, cfg, atoms = lvrf_setup
    vals, good, _ = _lvrf_queries(cfg, atoms, n_good=2, n_junk=0, seed=43)
    keys = jax.random.split(jax.random.PRNGKey(23), 2)

    def run(class_=None, obs_rec=None):
        eng = engine.Engine(spec, slots=2, sweeps_per_step=2)
        r = rt.Runtime(obs=obs_rec, failure=FAST_FAILURE)
        r.register("lvrf", eng)
        with r:
            gids = [r.submit("lvrf", good[i], keys=keys[i][None],
                             **({"class_": class_} if class_ else {}))
                    for i in range(2)]
            out = [r.result(g, timeout=RESULT_TIMEOUT_S) for g in gids]
        return r, [req.result for req in out]

    def assert_bit_equal(xs, ys):
        for x, y in zip(xs, ys):
            assert set(x) == set(y)
            for k in x:
                np.testing.assert_array_equal(np.asarray(x[k]),
                                              np.asarray(y[k]))

    r_plain, res_plain = run()
    r_null, res_null = run(class_="interactive")  # NULL recorder, labeled
    rec = obs.Recorder()
    r_obs, res_obs = run(class_="interactive", obs_rec=rec)
    assert_bit_equal(res_plain, res_null)
    assert_bit_equal(res_plain, res_obs)
    # NULL recorder recorded nothing, but SLO accounting still ran
    assert r_null.obs is obs.NULL
    assert r_null.stats()["slo"]["interactive"]["completed"] == 2
    assert rec.metrics.snapshot()["resolved"] == {
        "class=interactive,outcome=ok": 2}
