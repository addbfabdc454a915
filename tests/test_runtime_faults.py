"""Fault-tolerance chaos suite: supervision, quarantine, replay recovery.

The invariant everything here serves (the ISSUE's acceptance bar): under any
seeded FaultPlan, (a) every submitted future RESOLVES — a result or a
structured FaultError, never a hang — with healthy engines serving straight
through another engine's quarantine, and (b) replay-recovered results are
bit-equal to a fault-free run (the solo ``factorize(q, key)`` trajectory /
solo greedy decode the engines' serving contract already guarantees).

Layering mirrors the machinery: FaultPlan/ChaosEngine determinism is pure
host logic; supervision control flow (quarantine, restart budget, deadlines,
shedding, watchdog takeover, wedged stop) runs on cheap deterministic stub
engines; the recovery-replay bit-equality and the mixed nvsa+lvrf+lm chaos
run on the real engines.

Every blocking wait carries a timeout — these tests drive background
threads and must fail loudly instead of hanging CI (the workflow
additionally guards the chaos step with a hard job timeout).
"""
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro import runtime as rt
from repro.configs.registry import ARCHS
from repro.core import factorizer as fz
from repro.models import lvrf, nvsa
from repro.nn import transformer as T
from repro.runtime import faults as flt

RESULT_TIMEOUT_S = 300.0  # generous per-request wait; CI guards the whole step

FAST_FAILURE = rt.FailurePolicy(max_restarts=50, backoff_initial_s=0.01,
                                backoff_max_s=0.05, health_check_every=2)


# ---------------------------------------------------------------------------
# Stub engine: deterministic Steppable with scriptable faults (no jax)
# ---------------------------------------------------------------------------

class _StubRequest:
    def __init__(self, rid):
        self.id, self.result, self.latency_s = rid, rid, 0.0


class _StubEngine:
    """One request retired per step; faults scripted by step index."""

    def __init__(self, fail_on=(), recoverable=True, step_sleep=0.0):
        self._queue: list = []
        self._next = 0
        self.slots = 4
        self.steps = 0
        self.fail_on = set(fail_on)
        self.step_sleep = step_sleep
        self.recoveries_total = 0
        if not recoverable:
            self.recover = None  # not callable -> supervisor kills on fault

    def submit(self, payload, **kw):
        rid = self._next
        self._next += 1
        self._queue.append(rid)
        return rid

    def step(self):
        self.steps += 1
        if self.step_sleep:
            time.sleep(self.step_sleep)
        if self.steps in self.fail_on:
            raise ValueError(f"scripted fault at step {self.steps}")
        return [_StubRequest(self._queue.pop(0))] if self._queue else []

    def recover(self):
        self.recoveries_total += 1
        return len(self._queue)

    def cancel(self, rid):
        if rid in self._queue:
            self._queue.remove(rid)
            return True
        return False

    def drain(self):
        out = []
        while self._queue:
            out += self.step()
        return out

    @property
    def in_flight(self):
        return len(self._queue)

    def stats(self):
        return {"completed": self._next - len(self._queue)}


# ---------------------------------------------------------------------------
# FaultPlan / ChaosEngine: validation, determinism, transparency
# ---------------------------------------------------------------------------

def test_fault_plan_validates():
    with pytest.raises(ValueError):
        flt.FaultPlan(step_error_rate=1.5)
    with pytest.raises(ValueError):
        flt.FaultPlan(corrupt_rate=-0.1)
    with pytest.raises(ValueError):
        flt.FaultPlan(hang_rate=0.5)  # hang_rate needs a positive hang_s
    flt.FaultPlan(hang_rate=0.5, hang_s=0.01)  # ok


def _step_schedule(plan, n_steps, submit_every):
    """Drive a wrapped stub; return the per-step (error?, hang?) schedule."""
    ce = flt.ChaosEngine(_StubEngine(), plan, sleep=lambda s: None)
    sched = []
    for k in range(n_steps):
        if submit_every and k % submit_every == 0:
            try:
                ce.submit(None)
            except flt.InjectedFault:
                pass
        before = dict(ce.injected)
        try:
            ce.step()
        except flt.InjectedFault:
            pass
        sched.append((ce.injected["step_error"] - before["step_error"],
                      ce.injected["hang"] - before["hang"]))
    return sched


def test_chaos_schedule_is_pure_function_of_seed():
    """The k-th step's injection decision depends only on (seed, k) — not on
    how submits interleave (independent streams) and not on max_faults
    (draws are consumed even when the budget is exhausted)."""
    plan = flt.FaultPlan(seed=3, step_error_rate=0.3, hang_rate=0.2,
                         hang_s=1e-4, submit_reject_rate=0.5)
    a = _step_schedule(plan, 60, submit_every=1)
    b = _step_schedule(plan, 60, submit_every=7)  # different submit pattern
    assert a == b and sum(e for e, _ in a) > 0 and sum(h for _, h in a) > 0
    # max_faults truncates WHICH injections fire, not the stream positions:
    # the capped schedule is a prefix-masked copy of the uncapped one
    capped = _step_schedule(
        flt.FaultPlan(seed=3, step_error_rate=0.3, hang_rate=0.2, hang_s=1e-4,
                      submit_reject_rate=0.5, max_faults=2), 60, 1)
    fired = 0
    for (e, h), (ce_, ch) in zip(a, capped):
        if fired >= 2:
            assert (ce_, ch) == (0, 0)
        fired += e + h


def test_chaos_zero_rates_transparent():
    """At all-zero rates the wrapper forwards everything — protocol calls,
    optional capabilities, arbitrary attributes — and injects nothing (the
    property CI's REPRO_CHAOS_WRAP=1 transparency run rests on)."""
    inner = _StubEngine()
    ce = flt.ChaosEngine(inner, flt.FaultPlan(seed=0))
    assert isinstance(ce, rt.Steppable)
    assert rt.supports_recover(ce) and rt.supports_cancel(ce)
    assert ce.slots == 4  # attribute forwarding
    ids = [ce.submit(None) for _ in range(5)]
    out = []
    while ce.in_flight:
        out += ce.step()
    assert [r.id for r in out] == ids
    assert ce.stats()["chaos"] == {"step_error": 0, "hang": 0,
                                   "submit_reject": 0, "corrupt": 0,
                                   "storm": 0}


def test_maybe_chaos_wrap_env_gated(monkeypatch):
    eng = _StubEngine()
    monkeypatch.delenv("REPRO_CHAOS_WRAP", raising=False)
    assert flt.maybe_chaos_wrap(eng) is eng
    monkeypatch.setenv("REPRO_CHAOS_WRAP", "1")
    wrapped = flt.maybe_chaos_wrap(eng)
    assert isinstance(wrapped, flt.ChaosEngine) and wrapped.inner is eng
    assert wrapped.plan == flt.FaultPlan(seed=0)  # benign: all rates zero
    assert flt.maybe_chaos_wrap(wrapped) is wrapped  # no double wrap


# ---------------------------------------------------------------------------
# Supervision control flow on stubs: quarantine, budget, isolation
# ---------------------------------------------------------------------------

def test_quarantine_recovers_and_other_engines_keep_serving():
    r = rt.Runtime(failure=rt.FailurePolicy(backoff_initial_s=0.01))
    flaky, healthy = _StubEngine(fail_on=(2,)), _StubEngine()
    r.register("flaky", flaky)
    r.register("ok", healthy)
    with r:
        gids = [r.submit("flaky", None) for _ in range(5)]
        hids = [r.submit("ok", None) for _ in range(5)]
        for g in gids + hids:  # every future resolves with its result
            assert r.result(g, timeout=30).result is not None or True
    st = r.stats()
    assert st["flaky"]["supervision"]["state"] == "serving"
    assert st["flaky"]["supervision"]["restarts"] == 1
    assert st["flaky"]["telemetry"]["faults"] == 1
    assert st["flaky"]["telemetry"]["recoveries"] == 1
    assert flaky.recoveries_total == 1
    assert st["ok"]["supervision"]["restarts"] == 0  # isolation
    tags = [tag for _, tag in st["flaky"]["supervision"]["events"]]
    assert any(t.startswith("fault") for t in tags)
    assert any(t.startswith("quarantined") for t in tags)
    assert any(t.startswith("recovered") for t in tags)


def test_unrecoverable_engine_dies_others_serve():
    r = rt.Runtime()
    r.register("dies", _StubEngine(fail_on=(1,), recoverable=False))
    r.register("ok", _StubEngine())
    with r:
        g1 = r.submit("dies", None)
        g2 = r.submit("ok", None)
        with pytest.raises(flt.EngineDeadError) as ei:
            r.result(g1, timeout=30)
        assert ei.value.engine == "dies" and ei.value.kind == "dead"
        assert r.result(g2, timeout=30).result == 0  # healthy engine serves
        with pytest.raises(flt.EngineDeadError):  # fast-fail, no hang
            r.submit("dies", None)
    assert r.stats()["dies"]["supervision"]["state"] == "dead"


def test_restart_budget_exhaustion_kills():
    r = rt.Runtime(failure=rt.FailurePolicy(max_restarts=2,
                                            backoff_initial_s=0.005))
    r.register("flappy", _StubEngine(fail_on=set(range(1, 40))))
    with r:
        g = r.submit("flappy", None)
        with pytest.raises(flt.EngineDeadError):
            r.result(g, timeout=30)
    st = r.stats()["flappy"]["supervision"]
    assert st["state"] == "dead" and st["restarts"] == 2


def test_deadline_expires_and_sheds_are_structured():
    """Deadline misses fail the future with DeadlineExceededError (slot
    reclaimed via cancel); a full pending queue sheds at submit()."""
    r = rt.Runtime(max_pending=2)
    stuck = _StubEngine(step_sleep=0.01)
    stuck.step = lambda: (time.sleep(0.01), [])[1]  # never retires
    r.register("s", stuck)
    shed = 0
    with r:
        gids = []
        for _ in range(50):
            try:
                gids.append(r.submit("s", None, deadline_s=0.2))
            except flt.ShedError as e:
                assert e.kind == "shed" and e.engine == "s"
                shed += 1
        out = r.drain(timeout=30, return_exceptions=True)
    assert shed > 0 and len(out) == len(gids)  # every admitted future resolved
    assert all(isinstance(o, flt.DeadlineExceededError) for o in out)
    t = r.telemetry["s"]
    assert t.shed == shed and t.deadline_misses == len(gids)
    # satellite: shed/rejected requests never stamped the arrival estimator
    assert t.submitted == t.arrivals.observed == len(gids)


def test_watchdog_takeover_isolates_wedged_engine():
    """A step wedged past watchdog_s: that engine dies with WedgedError and
    a replacement stepper keeps serving the healthy engine — drain() and
    result() resolve instead of hanging behind the stuck thread."""
    r = rt.Runtime(watchdog_s=0.2)
    wedge, ok = _StubEngine(), _StubEngine()
    wedge.step = lambda: time.sleep(60)
    r.register("wedge", wedge)
    r.register("ok", ok)
    r.start()
    try:
        gw = r.submit("wedge", None)
        with pytest.raises(flt.WedgedError) as ei:
            r.result(gw, timeout=30)
        assert ei.value.engine == "wedge"
        go = r.submit("ok", None)  # the REPLACEMENT stepper serves this
        assert r.result(go, timeout=30).result == 0
        assert r.stats()["wedge"]["supervision"]["state"] == "dead"
    finally:
        r.stop(timeout=5)  # replacement stepper is healthy: joins fine


def test_stop_detects_wedged_join():
    """stop(timeout=) must not silently 'succeed' when the stepper cannot
    join: it warns, fails unfinished futures with WedgedError, refuses
    restart while the thread lives, and restarts cleanly once it exits."""
    r = rt.Runtime(watchdog_s=None)  # no takeover: exercise stop() itself
    wedge = _StubEngine()
    wedge.step = lambda: time.sleep(1.0)
    r.register("w", wedge)
    r.start()
    g = r.submit("w", None)
    time.sleep(0.1)  # let the stepper enter the slow step
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        r.stop(timeout=0.1)
    assert any("could not join" in str(w.message) for w in caught)
    with pytest.raises(flt.WedgedError):  # future failed, not hung
        r.result(g, timeout=5)
    with pytest.raises(RuntimeError, match="wedged"):
        r.start()  # the old thread still lives: restart refused
    deadline = time.monotonic() + 30
    while r._thread.is_alive() and time.monotonic() < deadline:
        time.sleep(0.05)  # the stale thread exits via its generation check
    wedge.step = lambda: []
    r.start()  # dead handle cleared: restart serves again
    r.stop()


# ---------------------------------------------------------------------------
# Real-engine recovery seams: replay bit-equality, cancel, health checks
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


def _assert_bit_equal_solo(req, q, key, spec):
    solo = fz.factorize(q, spec.codebooks, key, spec.cfg, spec.valid_mask)
    assert int(req.iterations[0]) == int(solo.iterations)
    np.testing.assert_array_equal(req.factorization.indices[0],
                                  np.asarray(solo.indices))
    np.testing.assert_allclose(req.factorization.reconstruction_sim[0],
                               float(solo.reconstruction_sim), rtol=1e-6)


def test_engine_recover_replays_bit_equal(lvrf_setup):
    """recover() mid-flight — even from CORRUPT state — replays every live
    row from its pinned key: results identical to a solo factorize().

    Junk queries hold the slots: they burn toward max_iters, so they are
    GUARANTEED mid-trajectory when the fault lands (clean LVRF queries
    converge in one iteration), and their garbage trajectory is still
    fully pinned by the key — replay bit-equality covers the divergent
    case, not just the easy one."""
    spec, cfg, atoms = lvrf_setup
    _, good, junk = _lvrf_queries(cfg, atoms, n_good=2, n_junk=2, seed=21)
    qs = list(junk) + list(good)  # junk first: they grab the 2 slots
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    eng = engine.Engine(spec, slots=2, sweeps_per_step=2)
    ids = [eng.submit(qs[i], keys=keys[i][None]) for i in range(4)]
    eng.step()  # 2 junk rows live mid-trajectory (max_iters >> 2 sweeps)
    inflight_before = eng.in_flight
    # poison the live state the way silent corruption would
    eng.state = eng.state._replace(est=eng.state.est.at[0].set(np.nan))
    assert eng.health_check() is not None
    replayed = eng.recover()
    assert replayed == 2 and eng.recoveries_total == 1
    assert eng.in_flight == inflight_before  # nothing lost nor duplicated
    assert eng.health_check() is None  # corrupt state discarded
    done = {r.id: r for r in eng.drain()}
    for i in range(4):
        _assert_bit_equal_solo(done[ids[i]], qs[i], keys[i], spec)


def test_engine_cancel_reclaims_slots_and_queue(lvrf_setup):
    spec, cfg, atoms = lvrf_setup
    vals, good, junk = _lvrf_queries(cfg, atoms, n_good=1, n_junk=3, seed=22)
    eng = engine.Engine(spec, slots=2, sweeps_per_step=2)
    jids = [eng.submit(junk[i]) for i in range(3)]  # 2 slotted + 1 queued
    eng.step()
    assert eng.cancel(jids[0]) and eng.cancel(jids[2])  # one live, one queued
    assert not eng.cancel(999)  # unknown id: nothing reclaimed
    assert eng.in_flight == 1
    gid = eng.submit(good[0])  # freed slot serves new work to completion
    done = {r.id: r for r in eng.drain()}
    assert set(done) == {jids[1], gid}  # cancelled ids never complete
    np.testing.assert_array_equal(np.asarray(done[gid].result["values"][0]),
                                  np.asarray(vals[0]))


def test_engine_health_check_flags_only_live_rows(lvrf_setup):
    spec, cfg, atoms = lvrf_setup
    _, good, junk = _lvrf_queries(cfg, atoms, n_good=0, n_junk=2, seed=23)
    eng = engine.Engine(spec, slots=2, sweeps_per_step=1)
    assert eng.health_check() is None  # idle engine: nothing to probe
    eng.submit(junk[0])
    eng.step()
    assert eng.health_check() is None  # healthy live row
    eng.state = eng.state._replace(est=eng.state.est.at[0].set(np.nan))
    msg = eng.health_check()
    assert msg is not None and "non-finite" in msg


def _solo_lm_tokens(cfg, params, prompt):
    """The 5 greedy tokens of ``prompt`` decoded alone on a one-slot
    LMEngine."""
    ref = rt.LMEngine(cfg, params, slots=1, max_len=32)
    ref.submit(prompt, max_new_tokens=5)
    return ref.drain()[0].tokens


def test_lm_engine_recover_replays_bit_equal():
    cfg_lm = ARCHS["llama3.2-3b"].smoke()
    params, _ = T.init(jax.random.PRNGKey(0), cfg_lm)
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (4 + i,), 0,
                                  cfg_lm.vocab) for i in range(2)]
    eng = rt.LMEngine(cfg_lm, params, slots=2, max_len=32)
    ids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.step()  # partial generations in flight
    assert eng.recover() == 2 and eng.recoveries_total == 1
    done = {r.id: r for r in eng.drain()}
    for p, rid in zip(prompts, ids):  # greedy decode: bit-equal re-generation
        want = _solo_lm_tokens(cfg_lm, params, p)
        assert done[rid].result["tokens"] == want


def test_engine_preempt_replays_bit_equal(lvrf_setup):
    """preempt() — the fleet controller's slot-reclaim seam — re-queues a
    live request through the same pinned-key contract as recover() and
    resize-shrink: the preempted rows restart from their keys and finish
    bit-equal to a solo factorize(), while the freed slot serves the
    higher-priority queued work first (priority fill)."""
    spec, cfg, atoms = lvrf_setup
    _, good, junk = _lvrf_queries(cfg, atoms, n_good=2, n_junk=2, seed=21)
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    eng = engine.Engine(spec, slots=2, sweeps_per_step=2)
    jids = [eng.submit(junk[i], keys=keys[i][None], priority=2)
            for i in range(2)]
    eng.step()  # junk grabs both slots, burning toward max_iters
    assert set(eng.live_requests()) == set(jids)
    gids = [eng.submit(good[i], keys=keys[2 + i][None], priority=0)
            for i in range(2)]  # higher priority, stuck behind live junk
    inflight_before = eng.in_flight
    assert eng.preempt(jids[0]) == 1  # one live row parked back on the queue
    assert eng.preempt(999) == 0  # unknown id: nothing to preempt
    assert eng.in_flight == inflight_before  # nothing lost nor duplicated
    assert jids[0] in eng.queued_requests()  # parked, not cancelled
    done = {r.id: r for r in eng.drain()}
    qs = list(junk) + list(good)
    for i, rid in enumerate(jids + gids):
        _assert_bit_equal_solo(done[rid], qs[i], keys[i], spec)


def test_lm_engine_preempt_replays_bit_equal():
    """A preempted mid-generation LM stream re-queues from its pinned
    prompt and regenerates bit-equal to an undisturbed solo decode — the
    same deterministic-replay argument as LM recover()."""
    cfg_lm = ARCHS["llama3.2-3b"].smoke()
    params, _ = T.init(jax.random.PRNGKey(0), cfg_lm)
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (4 + i,), 0,
                                  cfg_lm.vocab) for i in range(2)]
    eng = rt.LMEngine(cfg_lm, params, slots=2, max_len=32)
    ids = [eng.submit(p, max_new_tokens=5, priority=i) for i, p in
           enumerate(prompts)]
    eng.step()  # partial generations in flight
    assert eng.preempt(ids[1]) == 1
    assert eng.preempt(999) == 0
    done = {r.id: r for r in eng.drain()}
    for p, rid in zip(prompts, ids):
        want = _solo_lm_tokens(cfg_lm, params, p)
        assert done[rid].result["tokens"] == want


class _FailOnStep:
    """Minimal deterministic fault wrapper (independent of ChaosEngine):
    raises on scripted step indices, forwards everything else."""

    def __init__(self, inner, fail_steps):
        self.inner, self.fail_steps, self.steps = inner, set(fail_steps), 0

    def step(self):
        self.steps += 1
        if self.steps in self.fail_steps:
            raise flt.InjectedFault("scripted step fault")
        return self.inner.step()

    def __getattr__(self, name):
        return getattr(self.inner, name)


def test_runtime_quarantine_replay_bit_equal(lvrf_setup):
    """The tentpole end-to-end: a step fault mid-flight quarantines the
    engine, recovery replays the live rows from pinned keys, and every
    result is bit-equal to a fault-free (solo) run."""
    spec, cfg, atoms = lvrf_setup
    _, good, junk = _lvrf_queries(cfg, atoms, n_good=4, n_junk=2, seed=24)
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    inner = engine.Engine(spec, slots=4, sweeps_per_step=2)
    r = rt.Runtime(failure=FAST_FAILURE)
    r.register("lvrf", _FailOnStep(inner, fail_steps=(3,)))
    with r:
        gids = [r.submit("lvrf", good[i], keys=keys[i][None])
                for i in range(4)]
        # junk rows (pinned keys) burn toward max_iters: they are the live
        # mid-trajectory rows the fault hits and recovery replays
        jids = [r.submit("lvrf", junk[j], keys=keys[4 + j][None])
                for j in range(2)]
        reqs = [r.result(g, timeout=RESULT_TIMEOUT_S) for g in gids]
        jreqs = [r.result(g, timeout=RESULT_TIMEOUT_S) for g in jids]
    t = r.telemetry["lvrf"]
    assert t.faults == 1 and t.recoveries == 1 and t.replayed >= 1
    assert inner.recoveries_total == 1
    for i in range(4):
        _assert_bit_equal_solo(reqs[i], good[i], keys[i], spec)
    for j in range(2):  # the REPLAYED trajectories, bit-equal to fault-free
        _assert_bit_equal_solo(jreqs[j], junk[j], keys[4 + j], spec)


def test_runtime_deadline_zero_expires_and_engine_keeps_serving(lvrf_setup):
    spec, cfg, atoms = lvrf_setup
    vals, good, junk = _lvrf_queries(cfg, atoms, n_good=1, n_junk=1, seed=25)
    r = rt.Runtime()
    r.register("lvrf", engine.Engine(spec, slots=2, sweeps_per_step=2))
    with r:
        doomed = r.submit("lvrf", junk[0], deadline_s=0.0)
        ok = r.submit("lvrf", good[0])
        with pytest.raises(flt.DeadlineExceededError):
            r.result(doomed, timeout=RESULT_TIMEOUT_S)
        req = r.result(ok, timeout=RESULT_TIMEOUT_S)
        # failed handles stay retrievable; drain collects them structurally
        left = r.drain(timeout=RESULT_TIMEOUT_S, return_exceptions=True)
        assert all(isinstance(o, flt.DeadlineExceededError) for o in left)
    np.testing.assert_array_equal(np.asarray(req.result["values"][0]),
                                  np.asarray(vals[0]))
    assert r.telemetry["lvrf"].deadline_misses == 1


# ---------------------------------------------------------------------------
# The headline chaos run: seeded faults over mixed nvsa + lvrf + lm traffic
# ---------------------------------------------------------------------------

def test_chaos_mixed_traffic_every_future_resolves(lvrf_setup):
    """Seeded FaultPlans (step errors + state corruption on the factorizer
    engines, submit rejections + step errors on the LM) over concurrent
    nvsa + lvrf + lm traffic:

      (a) every admitted future resolves — a result or a structured
          FaultError — and the runtime stays serving end to end;
      (b) every factorization result is bit-equal to a solo factorize()
          with the same pinned key, and every LM result matches a solo
          LMEngine decode — i.e. replay-recovered trajectories are
          indistinguishable from a fault-free run.
    """
    spec_l, cfg_l, atoms = lvrf_setup
    cfg_n = nvsa.NVSAConfig()
    spec_n = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(0),
                                   cfg=cfg_n)
    cfg_lm = ARCHS["llama3.2-3b"].smoke()
    params, _ = T.init(jax.random.PRNGKey(0), cfg_lm)

    rng = np.random.default_rng(0)
    attrs = jnp.asarray(rng.integers(0, (5, 6, 10), (8, 3)))
    ctx = nvsa.target_query(spec_n.codebooks, attrs, cfg_n)
    nkeys = jax.random.split(jax.random.PRNGKey(5), 8)
    vals, good, junk = _lvrf_queries(cfg_l, atoms, n_good=6, n_junk=3, seed=9)
    lkeys = jax.random.split(jax.random.PRNGKey(6), 9)  # 6 good + 3 junk
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (4 + i,), 0,
                                  cfg_lm.vocab) for i in range(4)]

    lvrf_chaos = flt.ChaosEngine(
        engine.Engine(spec_l, slots=4, sweeps_per_step=2),
        flt.FaultPlan(seed=101, step_error_rate=0.12, corrupt_rate=0.08,
                      max_faults=3))
    nvsa_chaos = flt.ChaosEngine(
        engine.Engine(spec_n, slots=4),
        flt.FaultPlan(seed=202, step_error_rate=0.15, max_faults=2))
    lm_chaos = flt.ChaosEngine(
        rt.LMEngine(cfg_lm, params, slots=2, max_len=32),
        flt.FaultPlan(seed=303, step_error_rate=0.1, submit_reject_rate=0.3,
                      max_faults=3))

    r = rt.Runtime(failure=FAST_FAILURE)
    r.register("nvsa", nvsa_chaos)
    r.register("lvrf", lvrf_chaos)
    r.register("lm", lm_chaos)
    with r:
        g_n = r.submit("nvsa", ctx, keys=nkeys)
        g_l = [r.submit("lvrf", good[i], keys=lkeys[i][None])
               for i in range(6)]
        g_junk = [r.submit("lvrf", junk[j], keys=lkeys[6 + j][None])
                  for j in range(3)]
        g_dead = r.submit("lvrf", junk[0], deadline_s=0.0)  # guaranteed miss
        g_t = [r.submit("lm", p, max_new_tokens=5) for p in prompts]
        gids = [g_n] + g_l + g_junk + [g_dead] + g_t
        out = r.drain(timeout=RESULT_TIMEOUT_S, return_exceptions=True)

    # (a) EVERY future resolved, to a result or a STRUCTURED fault
    assert len(out) == len(gids)
    by_gid = dict(zip(sorted(gids), out))
    for gid, o in by_gid.items():
        if isinstance(o, Exception):
            assert isinstance(o, flt.FaultError), (gid, o)
    assert isinstance(by_gid[g_dead], flt.DeadlineExceededError)
    # engines were never killed: chaos stayed within the restart budget
    st = r.stats()
    assert all(st[n]["supervision"]["state"] == "serving"
               for n in ("nvsa", "lvrf", "lm"))
    # the plans actually fired (the run exercised recovery, not a quiet pass)
    injected = sum(sum(e.injected.values())
                   for e in (lvrf_chaos, nvsa_chaos, lm_chaos))
    assert injected > 0
    recoveries = sum(st[n]["telemetry"]["recoveries"]
                     for n in ("nvsa", "lvrf", "lm"))
    assert recoveries > 0

    # (b) surviving results are bit-equal to fault-free references
    req_n = by_gid[g_n]
    assert not isinstance(req_n, Exception)  # no submit faults on nvsa
    for i in range(8):
        solo = fz.factorize(ctx[i], spec_n.codebooks, nkeys[i], spec_n.cfg,
                            spec_n.valid_mask)
        assert int(req_n.iterations[i]) == int(solo.iterations)
        np.testing.assert_array_equal(req_n.factorization.indices[i],
                                      np.asarray(solo.indices))
    for i, g in enumerate(g_l):
        req = by_gid[g]
        assert not isinstance(req, Exception)  # no submit faults on lvrf
        _assert_bit_equal_solo(req, good[i], lkeys[i], spec_l)
        np.testing.assert_array_equal(np.asarray(req.result["values"][0]),
                                      np.asarray(vals[i]))
    for j, g in enumerate(g_junk):  # max_iters rows: live across any fault,
        req = by_gid[g]             # so these are the replayed trajectories
        assert not isinstance(req, Exception)
        _assert_bit_equal_solo(req, junk[j], lkeys[6 + j], spec_l)
    lm_rejects = 0
    for p, g in zip(prompts, g_t):
        o = by_gid[g]
        if isinstance(o, flt.InjectedFault):
            lm_rejects += 1  # rejected at submit: structured, not hung
            continue
        assert o.result["tokens"] == _solo_lm_tokens(cfg_lm, params, p)
    assert lm_rejects == lm_chaos.injected["submit_reject"]
