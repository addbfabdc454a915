"""Online serving runtime tests: EWMA estimator convergence, drift-triggered
re-tuning, warm-handoff bit-equality, async futures, cost-weighted stepping,
and the mixed NVSA + LVRF + LM acceptance path.

Every blocking wait in here carries a timeout — these tests drive a
background stepper thread and must fail loudly instead of hanging CI (the
workflow additionally guards the suite with a step-level timeout).
"""
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro import runtime as rt
from repro.configs.registry import ARCHS
from repro.core import factorizer as fz
from repro.engine.sharding.autotune import retune_slots
from repro.lm.paging import PagedConfig
from repro.models import lvrf, nvsa
from repro.nn import transformer as T

RESULT_TIMEOUT_S = 300.0  # generous per-request wait; CI guards the whole step


# ---------------------------------------------------------------------------
# Telemetry: the EWMA arrival estimator and the drift trigger
# ---------------------------------------------------------------------------

def _poisson_times(rate: float, n: int, seed: int, t0: float = 0.0):
    gaps = np.random.default_rng(seed).exponential(1.0 / rate, n)
    return t0 + np.cumsum(gaps)


def _converged_rate(est, times) -> float:
    """Feed arrivals; return the estimate time-averaged over the 2nd half
    (a single end-point EWMA read keeps ~sqrt(alpha) relative noise by
    construction; the re-tuner tolerates that, the convergence assertion
    should not)."""
    samples = []
    for i, t in enumerate(times):
        est.observe(t)
        if i >= len(times) // 2:
            samples.append(est.rate(t))
    return float(np.mean(samples))


def test_ewma_converges_to_poisson_rate():
    est = rt.ArrivalEstimator(alpha=0.05)
    times = _poisson_times(50.0, 4000, seed=0)
    assert _converged_rate(est, times) == pytest.approx(50.0, rel=0.12)
    # tracks a rate change: the same estimator re-converges to 200 rps
    times2 = _poisson_times(200.0, 4000, seed=1, t0=times[-1])
    assert _converged_rate(est, times2) == pytest.approx(200.0, rel=0.12)


def test_ewma_idle_decay():
    est = rt.ArrivalEstimator(alpha=0.1)
    times = _poisson_times(10.0, 500, seed=2)
    for t in times:
        est.observe(t)
    busy = est.rate(times[-1])
    assert 5.0 < busy < 20.0  # in the right regime (end-point read is noisy)
    # 100 s of silence: the still-open gap must drag the estimate down
    assert est.rate(times[-1] + 100.0) < 0.2 * busy


def test_should_retune_triggers_exactly_at_threshold():
    # no baseline / no traffic: never triggers
    assert not rt.should_retune(5.0, None, 2.0)
    assert not rt.should_retune(0.0, 5.0, 2.0)
    # ratio just inside the threshold: quiet, both directions
    assert not rt.should_retune(1.999, 1.0, 2.0)
    assert not rt.should_retune(1.0 / 1.999, 1.0, 2.0)
    # at/past the threshold: triggers, both directions
    assert rt.should_retune(2.0, 1.0, 2.0)
    assert rt.should_retune(7.3, 1.0, 2.0)
    assert rt.should_retune(0.5, 1.0, 2.0)
    with pytest.raises(ValueError):
        rt.should_retune(1.0, 1.0, 1.0)


def test_telemetry_step_cost_ewma_units_and_idle_steps():
    """The step-time EWMA is wall seconds PER STEP UNIT (sweep) and idle
    steps (zero units) must not dilute it — the measured cost basis
    _maybe_retune hands to retune_slots so both sides of the service-vs-
    arrival comparison are wall-clock (the unit-mismatch satellite)."""
    t = rt.telemetry.EngineTelemetry()
    assert t.step_unit_s() is None
    t.on_step(1.0, 4, step_s=0.4, units=4)  # 0.1 s / sweep
    assert t.step_unit_s() == pytest.approx(0.1)
    before = t.step_unit_s()
    t.on_step(0.0, 0)  # idle step: no timing info, EWMA untouched
    t.on_step(0.0, 0, step_s=0.5, units=0)  # zero units: ignored too
    assert t.step_unit_s() == before
    t.on_step(1.0, 4, step_s=0.8, units=4)  # 0.2 s/sweep -> EWMA moves up
    assert before < t.step_unit_s() < 0.2
    assert t.snapshot()["step_unit_s"] == t.step_unit_s()


def test_runtime_records_wall_clock_step_cost(lvrf_setup):
    """A served runtime leaves a positive measured step-cost estimate in
    telemetry (the stepper times every busy engine step for free) — and the
    FIRST busy step of a program generation, which pays JIT compilation, is
    excluded so it cannot poison the re-tune cost basis."""
    spec, cfg, atoms = lvrf_setup
    # junk rows never converge, so the engine runs many busy steps past the
    # compile-bearing first one
    _, good, junk = _lvrf_queries(cfg, atoms, n_good=4, n_junk=2, seed=11)
    r = rt.Runtime()
    r.register("lvrf", engine.Engine(spec, slots=2, sweeps_per_step=2))
    with r:
        gids = [r.submit("lvrf", good[i]) for i in range(4)]
        for j in range(2):
            r.submit("lvrf", junk[j])
        for g in gids:
            r.result(g, timeout=RESULT_TIMEOUT_S)
        r.drain(timeout=RESULT_TIMEOUT_S)
    t = r.telemetry["lvrf"]
    assert t.step_unit_s() is not None and t.step_unit_s() > 0
    # steady-state sweeps are milliseconds; a compile-contaminated EWMA
    # (first-step compile is ~seconds) would sit orders of magnitude higher
    assert t.step_unit_s() < 1.0


# ---------------------------------------------------------------------------
# Warm-handoff resize (the re-tune mechanism) on the real engine
# ---------------------------------------------------------------------------

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


def test_resize_warm_handoff_bit_equal(lvrf_setup):
    """Grow AND shrink mid-flight (junk rows keep slots busy at max_iters):
    every request stays bit-equal to a solo factorize() and to an untouched
    engine serving the same submissions."""
    spec, cfg, atoms = lvrf_setup
    vals, good, junk = _lvrf_queries(cfg, atoms, n_good=6, n_junk=3, seed=11)
    keys = jax.random.split(jax.random.PRNGKey(42), 6)

    def serve(resizes):
        eng = engine.Engine(spec, slots=4, sweeps_per_step=2)
        ids = [eng.submit(good[i], keys=keys[i][None]) for i in range(6)]
        for j in range(3):
            eng.submit(junk[j])
        fin = []
        for slots in resizes:
            fin += eng.step()
            before = eng.in_flight
            eng.resize(slots)
            assert eng.in_flight == before  # nothing lost in the handoff
            assert eng.slots == slots
        fin += eng.drain()
        return eng, ids, {r.id: r for r in fin}

    eng, ids, done = serve(resizes=(8, 2))
    assert eng.resizes_total == 2
    _, ref_ids, ref_done = serve(resizes=())
    for i in range(6):
        solo = fz.factorize(good[i], spec.codebooks, keys[i], spec.cfg,
                            spec.valid_mask)
        for req in (done[ids[i]], ref_done[ref_ids[i]]):
            assert int(req.iterations[0]) == int(solo.iterations)
            np.testing.assert_array_equal(req.factorization.indices[0],
                                          np.asarray(solo.indices))
            np.testing.assert_allclose(
                req.factorization.reconstruction_sim[0],
                float(solo.reconstruction_sim), rtol=1e-6)


def test_resize_rederives_burst_unless_pinned(lvrf_setup):
    spec, _, _ = lvrf_setup
    eng = engine.Engine(spec, slots=4)
    derived16 = engine.derive_sweeps_per_step(spec, 16)
    eng.resize(16)
    assert eng.sweeps_per_step == derived16
    pinned = engine.Engine(spec, slots=4, sweeps_per_step=3)
    pinned.resize(16)
    assert pinned.sweeps_per_step == 3


def test_retune_slots_entry_point(lvrf_setup):
    spec, _, _ = lvrf_setup
    eng = engine.Engine(spec, slots=4)
    # forced candidate set: a different verdict returns the new global count
    assert retune_slots(eng, 5.0, candidates=(8,)) == 8
    # same verdict as current: no-op
    assert retune_slots(eng, 5.0, candidates=(4,)) is None
    # non-factorizer engines are never re-tuned
    assert retune_slots(
        types.SimpleNamespace(spec=types.SimpleNamespace(cfg=None), slots=4),
        5.0) is None


# ---------------------------------------------------------------------------
# Engine.stats(): rolling percentile window (satellite)
# ---------------------------------------------------------------------------

def test_engine_stats_rolling_window(lvrf_setup):
    spec, cfg, atoms = lvrf_setup
    _, good, _ = _lvrf_queries(cfg, atoms, n_good=5, n_junk=0, seed=3)
    eng = engine.Engine(spec, slots=4)
    for i in range(3):
        eng.submit(good[i])
    eng.drain()
    st = eng.stats()
    assert st["completed"] == 3 and st["window_completed"] == 3
    assert st["latency_p50_ms"] is not None
    for i in range(3, 5):
        eng.submit(good[i])
    eng.drain()
    st = eng.stats()  # only the 2 new completions are in the window
    assert st["completed"] == 5 and st["window_completed"] == 2
    assert st["latency_p50_ms"] is not None
    st = eng.stats()  # empty window: percentiles None, totals persist
    assert st["completed"] == 5 and st["window_completed"] == 0
    assert st["latency_p50_ms"] is None and st["latency_mean_all_ms"] is not None


# ---------------------------------------------------------------------------
# Runtime: async submit/result, error isolation, cost-weighted stepping
# ---------------------------------------------------------------------------

def test_runtime_async_submit_futures(lvrf_setup):
    spec, cfg, atoms = lvrf_setup
    vals, good, _ = _lvrf_queries(cfg, atoms, n_good=6, n_junk=0, seed=5)
    r = rt.Runtime()
    r.register("lvrf", engine.Engine(spec, slots=4))
    with pytest.raises(KeyError):
        r.submit("nope", good[0])
    with r:
        gids = [r.submit("lvrf", good[i]) for i in range(6)]
        reqs = [r.result(g, timeout=RESULT_TIMEOUT_S) for g in gids]
    got = np.stack([np.asarray(q.result["values"][0]) for q in reqs])
    np.testing.assert_array_equal(got, np.asarray(vals))
    with pytest.raises(KeyError):
        r.result(10_000)
    st = r.stats()["lvrf"]
    assert st["completed"] == 6
    assert st["telemetry"]["submitted"] == 6
    assert st["telemetry"]["arrival_rate_rps"] > 0


def test_runtime_bad_request_fails_only_its_future(lvrf_setup):
    spec, cfg, atoms = lvrf_setup
    vals, good, _ = _lvrf_queries(cfg, atoms, n_good=1, n_junk=0, seed=6)
    cfg_lm = ARCHS["llama3.2-3b"].smoke()
    params, _ = T.init(jax.random.PRNGKey(0), cfg_lm)
    r = rt.Runtime()
    r.register("lvrf", engine.Engine(spec, slots=2))
    r.register("lm", rt.LMEngine(cfg_lm, params, slots=2, max_len=8))
    with r:
        bad = r.submit("lm", jnp.arange(20, dtype=jnp.int32))  # > max_len
        ok = r.submit("lvrf", good[0])
        with pytest.raises(ValueError):
            r.result(bad, timeout=RESULT_TIMEOUT_S)
        req = r.result(ok, timeout=RESULT_TIMEOUT_S)  # runtime still serving
    np.testing.assert_array_equal(np.asarray(req.result["values"][0]),
                                  np.asarray(vals[0]))


def test_runtime_stop_fails_unfinished_and_restarts_clean(lvrf_setup):
    """stop() mid-flight fails outstanding futures loudly (no silent hang),
    rejects further submits, and a restart serves fresh requests without
    tripping over the pre-stop bookkeeping."""
    spec, cfg, atoms = lvrf_setup
    vals, good, junk = _lvrf_queries(cfg, atoms, n_good=1, n_junk=1, seed=13)
    r = rt.Runtime()
    r.register("lvrf", engine.Engine(spec, slots=2, sweeps_per_step=1))
    r.start()
    gid = r.submit("lvrf", junk[0])  # max_iters row: in flight for a while
    r.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        r.result(gid, timeout=10)
    with pytest.raises(RuntimeError, match="stopped"):
        r.submit("lvrf", good[0])
    r.start()
    g2 = r.submit("lvrf", good[0])
    req = r.result(g2, timeout=RESULT_TIMEOUT_S)
    r.stop()
    np.testing.assert_array_equal(np.asarray(req.result["values"][0]),
                                  np.asarray(vals[0]))


class _StubRequest:
    def __init__(self, rid):
        self.id, self.result, self.latency_s = rid, rid, 0.0


class _StubEngine:
    """Deterministic Steppable: one request retired per step, fixed modeled
    step cost (no jax; proves the protocol is structural)."""

    def __init__(self, cost_s: float, log: list, tag: str):
        self._cost, self._log, self._tag = cost_s, log, tag
        self._queue: list = []
        self._next = 0
        self.slots = 1

    def submit(self, payload, **kw):
        rid = self._next
        self._next += 1
        self._queue.append(rid)
        return rid

    def step(self):
        self._log.append(self._tag)
        return [_StubRequest(self._queue.pop(0))] if self._queue else []

    def drain(self):
        out = []
        while self._queue:
            out += self.step()
        return out

    @property
    def in_flight(self):
        return len(self._queue)

    def step_cost_s(self):
        return self._cost

    def stats(self):
        return {"completed": self._next - len(self._queue)}


def test_runtime_cost_weighted_stepping_no_starvation():
    """A cheap engine with a deep queue must not alternate 1:1 behind an
    expensive one: virtual time advances by step cost / backlog, so the
    1000x-cheaper engine drains while the expensive engine has taken at
    most a couple of steps."""
    log: list = []
    cheap = _StubEngine(1e-6, log, "cheap")
    costly = _StubEngine(1e-3, log, "costly")
    assert isinstance(cheap, rt.Steppable)
    r = rt.Runtime()
    r.register("cheap", cheap)
    r.register("costly", costly)
    with r:
        gids = [r.submit("cheap", None) for _ in range(50)]
        gids += [r.submit("costly", None) for _ in range(50)]
        for g in gids:
            r.result(g, timeout=RESULT_TIMEOUT_S)
    last_cheap = max(i for i, t in enumerate(log) if t == "cheap")
    costly_before = sum(1 for t in log[:last_cheap] if t == "costly")
    assert costly_before <= 5, (costly_before, log[:60])


# ---------------------------------------------------------------------------
# EWMA-driven re-tune through the runtime + the mixed-traffic acceptance bar
# ---------------------------------------------------------------------------

def test_runtime_ewma_drift_triggers_retune(lvrf_setup):
    """A submit burst far above the policy baseline must re-tune the engine
    (EWMA drift -> choose_slots -> warm resize) while results stay exact."""
    spec, cfg, atoms = lvrf_setup
    vals, good, junk = _lvrf_queries(cfg, atoms, n_good=8, n_junk=4, seed=7)
    eng = engine.Engine(spec, slots=4, sweeps_per_step=2)
    r = rt.Runtime()
    r.register("lvrf", eng, retune=rt.RetunePolicy(
        threshold=2.0, check_every=1, baseline_rps=1e-3, candidates=(8,)))
    with r:
        gids = [r.submit("lvrf", good[i]) for i in range(8)]
        for j in range(4):
            r.submit("lvrf", junk[j])  # max_iters rows keep the engine busy
        reqs = [r.result(g, timeout=RESULT_TIMEOUT_S) for g in gids]
        r.drain(timeout=RESULT_TIMEOUT_S)
    assert r.telemetry["lvrf"].retunes >= 1
    assert eng.slots == 8 and eng.resizes_total >= 1
    got = np.stack([np.asarray(q.result["values"][0]) for q in reqs])
    np.testing.assert_array_equal(got, np.asarray(vals))


@pytest.mark.parametrize("paged", [None, PagedConfig()],
                         ids=["contiguous", "paged"])
def test_runtime_mixed_traffic_bit_equal_acceptance(lvrf_setup, paged):
    """The ISSUE acceptance bar: one Runtime serves concurrent
    nvsa_abduction + lvrf_rows + lm_decode traffic from its background
    thread; every factorization request is bit-equal to a solo factorize()
    with the same key ACROSS a mid-run EWMA-triggered re-tune, and LM
    outputs on either KV layout match a solo contiguous LMEngine."""
    spec_l, cfg_l, atoms = lvrf_setup
    cfg_n = nvsa.NVSAConfig()
    spec_n = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(0),
                                   cfg=cfg_n)
    cfg_lm = ARCHS["llama3.2-3b"].smoke()
    params, _ = T.init(jax.random.PRNGKey(0), cfg_lm)

    rng = np.random.default_rng(0)
    # NVSA: one task of 8 context queries with pinned per-query keys
    attrs = jnp.asarray(rng.integers(0, (5, 6, 10), (8, 3)))
    ctx = nvsa.target_query(spec_n.codebooks, attrs, cfg_n)
    nkeys = jax.random.split(jax.random.PRNGKey(5), 8)
    # LVRF rows (pinned keys) + junk to keep the engine busy through re-tune
    vals, good, junk = _lvrf_queries(cfg_l, atoms, n_good=6, n_junk=3, seed=9)
    lkeys = jax.random.split(jax.random.PRNGKey(6), 6)
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (4 + i,), 0,
                                  cfg_lm.vocab) for i in range(2)]

    lvrf_eng = engine.Engine(spec_l, slots=4, sweeps_per_step=2)
    r = rt.Runtime()
    r.register("nvsa", engine.Engine(spec_n, slots=4))
    r.register("lvrf", lvrf_eng, retune=rt.RetunePolicy(
        threshold=2.0, check_every=1, baseline_rps=1e-3, candidates=(8,)))
    r.register("lm", rt.LMEngine(cfg_lm, params, slots=2, max_len=32,
                                 paged=paged))
    with r:
        g_n = r.submit("nvsa", ctx, keys=nkeys)
        g_l = [r.submit("lvrf", good[i], keys=lkeys[i][None])
               for i in range(6)]
        for j in range(3):
            r.submit("lvrf", junk[j])
        g_t = [r.submit("lm", p, max_new_tokens=5) for p in prompts]
        req_n = r.result(g_n, timeout=RESULT_TIMEOUT_S)
        req_l = [r.result(g, timeout=RESULT_TIMEOUT_S) for g in g_l]
        req_t = [r.result(g, timeout=RESULT_TIMEOUT_S) for g in g_t]
        r.drain(timeout=RESULT_TIMEOUT_S)

    # the EWMA re-tune really happened mid-run
    assert r.telemetry["lvrf"].retunes >= 1 and lvrf_eng.slots == 8
    # factorization bit-equality vs solo runs (both engines, every query)
    for i in range(8):
        solo = fz.factorize(ctx[i], spec_n.codebooks, nkeys[i], spec_n.cfg,
                            spec_n.valid_mask)
        assert int(req_n.iterations[i]) == int(solo.iterations)
        np.testing.assert_array_equal(req_n.factorization.indices[i],
                                      np.asarray(solo.indices))
    for i in range(6):
        solo = fz.factorize(good[i], spec_l.codebooks, lkeys[i], spec_l.cfg,
                            spec_l.valid_mask)
        assert int(req_l[i].iterations[0]) == int(solo.iterations)
        np.testing.assert_array_equal(req_l[i].factorization.indices[0],
                                      np.asarray(solo.indices))
        np.testing.assert_array_equal(np.asarray(req_l[i].result["values"][0]),
                                      np.asarray(vals[i]))
    # LM parity vs a solo LMEngine decode of the same prompts
    for p, req in zip(prompts, req_t):
        ref = rt.LMEngine(cfg_lm, params, slots=1, max_len=32)
        ref.submit(p, max_new_tokens=5)
        assert req.result["tokens"] == ref.drain()[0].tokens
    # every engine reports through the merged stats path, plus the
    # per-class SLO section (register() reserves the "slo" name)
    st = r.stats()
    assert set(st) == {"nvsa", "lvrf", "lm", "slo"}
    assert st["lm"]["tokens_total"] == 10
    assert st["lvrf"]["telemetry"]["retunes"] >= 1
    assert st["slo"]["lm"]["completed"] == 2
