"""Engine serving tests: StageGraph lowering, adSCH planning, continuous
batching invariants, and parity with the in-process solve paths."""
import logging
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import factorizer as fz
from repro.core.scheduler import Op
from repro.engine.build import PipelinePlan, build_pipeline, plan_interleave
from repro.engine.stage import Stage, StageGraph
from repro.models import cnn, lvrf, nvsa


# ---------------------------------------------------------------------------
# StageGraph lowering: scheduler-chosen lag respected, outputs exact
# ---------------------------------------------------------------------------

def _toy_graph(sym_dims=(2048, 256), n_sym=8):
    """3-stage graph with closed-form fns (so any lowering is checkable)."""
    sym_ops, prev = [], ()
    for i in range(n_sym):  # a chain of sweeps, like the resonator loop
        op = Op(f"c{i}", "circconv", sym_dims, deps=prev, symbolic=True)
        sym_ops.append(op)
        prev = (op.name,)
    return StageGraph("toy", (
        Stage("n1", lambda x, k: x * 2.0, symbolic=False,
              cost_ops=(Op("g1", "gemm", (4096, 512, 512)),)),
        Stage("n2", lambda x, k: x + 1.0, symbolic=False,
              cost_ops=(Op("g2", "gemm", (4096, 512, 512)),)),
        Stage("s1", lambda x, k: x * x, symbolic=True,
              cost_ops=tuple(sym_ops)),
    ))


def _reference(graph, xs, key):
    T = xs.shape[0]
    keys = jax.random.split(key, T)
    outs = []
    for t in range(T):
        x = xs[t]
        for st in graph.stages:
            x = st.fn(x, keys[t])
        outs.append(x)
    return jnp.stack(outs)


@pytest.mark.parametrize("lags", [(0, 0), (1, 0), (0, 1), (1, 1)])
def test_lowered_scan_matches_reference_at_every_depth(lags):
    g = _toy_graph()
    plan = PipelinePlan(lags, (1.0,) * len(lags), 0.0, 0.0)
    runner = build_pipeline(g, plan=plan)
    assert runner.depth == 1 + sum(lags)
    assert sum(len(p) for p in runner.phase_names) == 3
    xs = jax.random.normal(jax.random.PRNGKey(0), (5, 4, 8))
    got = runner(xs, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_reference(g, xs, jax.random.PRNGKey(1))))


def test_lowered_scan_short_stream_deeper_than_T():
    g = _toy_graph()
    plan = PipelinePlan((1, 1), (1.0, 1.0), 0.0, 0.0)
    runner = build_pipeline(g, plan=plan)  # depth 3 > T
    xs = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8))
    got = runner(xs, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_reference(g, xs, jax.random.PRNGKey(1))))


def test_plan_interleave_is_cost_driven():
    """The lag is an adSCH estimate, not a constant: a sweep-chained symbolic
    tail that hides in the neural window gets a one-batch lag; a tail that
    dwarfs the window (or one too tiny to pay for the reserved cell sliver)
    does not."""
    mid = plan_interleave(_toy_graph(sym_dims=(2048, 256), n_sym=8))
    tiny = plan_interleave(_toy_graph(sym_dims=(64, 64), n_sym=1))
    huge = plan_interleave(_toy_graph(sym_dims=(8192, 512), n_sym=8))
    assert mid.lags[-1] == 1, mid
    assert tiny.lags[-1] == 0, tiny
    assert huge.lags[-1] == 0, huge
    assert build_pipeline(_toy_graph((2048, 256), 8)).depth > \
        build_pipeline(_toy_graph((8192, 512), 8)).depth


def test_nvsa_plan_pipelines_the_neural_symbolic_boundary():
    cfg = nvsa.NVSAConfig()
    g = nvsa.stage_graph(None, None, None, cfg, batch=2)
    assert not g.runnable  # cost-model-only graph still plannable
    plan = plan_interleave(g)
    assert plan.lags == (1,)
    assert plan.gains[0] > 1.0


# ---------------------------------------------------------------------------
# NVSA through the engine: parity with solve()
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def nvsa_setup():
    cfg = nvsa.NVSAConfig()
    cbs, mask = nvsa.make_codebooks(jax.random.PRNGKey(0), cfg)
    params = cnn.init(jax.random.PRNGKey(1), cfg.cnn)
    return cfg, cbs, mask, params


def test_pipelined_stream_bit_equals_per_batch_solve(nvsa_setup):
    cfg, cbs, mask, params = nvsa_setup
    B, T = 2, 3
    runner = build_pipeline(nvsa.stage_graph(params, cbs, mask, cfg, batch=B))
    assert runner.depth == 2  # scheduler-chosen one-batch lag
    imgs = jax.random.uniform(jax.random.PRNGKey(2), (T, B, 9, 32, 32))
    cands = jax.random.uniform(jax.random.PRNGKey(3), (T, B, 8, 32, 32))
    got = np.asarray(runner((imgs, cands), jax.random.PRNGKey(7)))
    keys = jax.random.split(jax.random.PRNGKey(7), T)
    want = np.stack([np.asarray(nvsa.solve(
        params, {"images": imgs[t], "candidate_images": cands[t]},
        cbs, mask, keys[t], cfg)["answer"]) for t in range(T)])
    np.testing.assert_array_equal(got, want)


def test_pipelined_solve_scan_is_deprecated_wrapper(nvsa_setup):
    cfg, cbs, mask, params = nvsa_setup
    imgs = jax.random.uniform(jax.random.PRNGKey(2), (2, 1, 9, 32, 32))
    cands = jax.random.uniform(jax.random.PRNGKey(3), (2, 1, 8, 32, 32))
    with pytest.warns(DeprecationWarning):
        ans = nvsa.pipelined_solve_scan(params, imgs, cands, cbs, mask,
                                        jax.random.PRNGKey(5), cfg)
    assert np.asarray(ans).shape == (2, 1)


def test_engine_request_answers_bit_equal_solve(nvsa_setup):
    """One RPM task through Engine.submit/drain == nvsa.solve, bit for bit,
    even with fewer slots than queries (rows are independent)."""
    cfg, cbs, mask, params = nvsa_setup
    batch = {"images": jax.random.uniform(jax.random.PRNGKey(2), (1, 9, 32, 32)),
             "candidate_images": jax.random.uniform(jax.random.PRNGKey(3),
                                                    (1, 8, 32, 32))}
    key = jax.random.PRNGKey(11)
    want = nvsa.solve(params, batch, cbs, mask, key, cfg)

    ctx = nvsa.perceive(params, batch["images"][:, :8], cfg, cbs)[0]  # [8, D]
    cand = nvsa.perceive(params, batch["candidate_images"], cfg, cbs)[0]
    k1, _ = jax.random.split(key)
    qkeys = jax.random.split(k1, 8)  # solve's per-query key layout

    spec = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(0),
                                 cfg=cfg, params=params, batch=1)
    eng = engine.Engine(spec, slots=3)  # fewer slots than queries
    eng.submit(ctx, keys=qkeys, meta={"cand": cand})
    (req,) = eng.drain()
    assert req.result["answer"] == int(want["answer"][0])
    np.testing.assert_array_equal(req.iterations,
                                  np.asarray(want["fact_iters"][0]))
    np.testing.assert_allclose(np.asarray(req.result["sims"]),
                               np.asarray(want["sims"][0]), rtol=1e-5)


def _nvsa_tail_inputs(seed, cfg, cbs):
    """Seeded context queries, factorizer result and candidates of one task:
    noisy bound atoms, so beliefs and answers are not degenerate."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    attrs = jnp.stack([jax.random.randint(k, (16,), 0, n) for k, n in
                       zip(jax.random.split(ks[0], 3), nvsa.ATTR_SIZES)], -1)
    qs = nvsa.target_query(cbs, attrs, cfg)
    qs = qs + 0.3 * jax.random.normal(ks[1], qs.shape)
    scores = jnp.einsum("fmd,nd->nfm", cbs, qs[:8])
    scores = scores + 0.05 * jax.random.normal(ks[2], scores.shape)
    res = fz.FactorizerResult(
        np.asarray(attrs[:8]), np.full(8, 3, np.int32), np.ones(8, bool),
        np.ones(8, np.float32), np.asarray(scores, np.float32))
    return qs[:8], res, np.asarray(qs[8:])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_nvsa_postprocess_matches_eager_tail(nvsa_setup, seed):
    """The spec's compiled postprocess == the eager beliefs_from_scores ->
    abduce_answers composition, with the result keys, types and shapes the
    callers read; the no-``cand`` path returns the beliefs alone."""
    cfg, _, _, _ = nvsa_setup
    spec = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(0),
                                 cfg=cfg)
    queries, res, cand = _nvsa_tail_inputs(seed, cfg, spec.codebooks)
    beliefs = nvsa.beliefs_from_scores(queries, jnp.asarray(res.scores),
                                       spec.valid_mask, cfg)
    answer, sims = nvsa.abduce_answers(beliefs[None], jnp.asarray(cand)[None],
                                       spec.codebooks, cfg)

    out = spec.postprocess(queries, res, {"cand": cand})
    assert set(out) == {"indices", "iterations", "converged", "beliefs",
                        "answer", "sims"}
    assert type(out["answer"]) is int and out["answer"] == int(answer[0])
    assert np.asarray(out["sims"]).shape == (8,)
    np.testing.assert_allclose(out["sims"], np.asarray(sims[0]), rtol=1e-5)
    np.testing.assert_allclose(out["beliefs"], np.asarray(beliefs),
                               rtol=1e-5)
    np.testing.assert_array_equal(out["indices"], res.indices)

    bare = spec.postprocess(queries, res, None)
    assert set(bare) == {"indices", "iterations", "converged", "beliefs"}
    assert np.asarray(bare["beliefs"]).shape == (8, len(nvsa.ATTR_SIZES),
                                                 nvsa.MAX_M)
    np.testing.assert_allclose(bare["beliefs"], np.asarray(beliefs),
                               rtol=1e-5)


def test_nvsa_postprocess_compiles_once(nvsa_setup, caplog):
    """The tail is one program per spec: the first call compiles exactly
    that program (eager primitives would compile many, or none once their
    caches are warm), and later calls at the same shapes compile nothing."""
    cfg, _, _, _ = nvsa_setup
    spec = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(0),
                                 cfg=cfg)

    def compiled(seed):
        queries, res, cand = _nvsa_tail_inputs(seed, cfg, spec.codebooks)
        caplog.clear()
        with caplog.at_level(logging.WARNING), jax.log_compiles():
            spec.postprocess(queries, res, {"cand": cand})
        return [r.getMessage().split(" with ")[0] for r in caplog.records
                if r.getMessage().startswith("Compiling ")]

    assert compiled(3) == ["Compiling jit(abduction_tail)"]
    assert compiled(4) == []
    assert compiled(5) == []


# ---------------------------------------------------------------------------
# Continuous batching invariants (LVRF: second registered workload)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lvrf_setup():
    spec = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0))
    cfg = lvrf.LVRFConfig()
    atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(0))[0], cfg)
    return spec, cfg, atoms


def test_engine_serves_second_workload(lvrf_setup):
    spec, cfg, atoms = lvrf_setup
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.integers(0, cfg.n_values, (6, 3)))
    qs = lvrf.encode_row(atoms, vals, cfg)
    eng = engine.Engine(spec, slots=4)
    for i in range(6):
        eng.submit(qs[i])
    done = eng.drain()
    got = np.stack([np.asarray(r.result["values"][0]) for r in done])
    np.testing.assert_array_equal(got, np.asarray(vals))
    assert all(bool(r.result["converged"].all()) for r in done)


def test_slotting_invariants_no_starvation_and_refill(lvrf_setup):
    """More requests than slots, including never-converging junk queries:
    every request retires (no starvation), retired slots are refilled, and
    junk rows stop at exactly max_iters."""
    spec, cfg, atoms = lvrf_setup
    rng = np.random.default_rng(1)
    n_good, n_junk = 10, 3
    vals = jnp.asarray(rng.integers(0, cfg.n_values, (n_good, 3)))
    good = lvrf.encode_row(atoms, vals, cfg)
    junk = jnp.asarray(rng.normal(size=(n_junk, cfg.vsa.dim)), jnp.float32)
    eng = engine.Engine(spec, slots=4, sweeps_per_step=2)
    ids = [eng.submit(good[i]) for i in range(n_good)]
    ids += [eng.submit(junk[i]) for i in range(n_junk)]
    done = eng.drain()
    assert sorted(r.id for r in done) == sorted(ids)  # nobody starves
    assert eng.in_flight == 0
    by_id = {r.id: r for r in done}
    for i in range(n_junk):
        r = by_id[ids[n_good + i]]
        assert int(r.iterations[0]) == spec.cfg.max_iters
        assert not bool(r.factorization.converged[0])
    # with 4 slots and 13 requests the engine must have recycled slots
    assert eng.steps_total > 1
    # total sweeps is bounded by the junk queries' budget plus slack — a
    # batch-and-wait wave scheme would need ceil(13/4)=4 waves of max_iters
    assert eng.sweeps_total < 2 * spec.cfg.max_iters


def test_per_request_iterations_match_solo_runs(lvrf_setup):
    """A request's trajectory must not depend on its slot or batch-mates."""
    spec, cfg, atoms = lvrf_setup
    rng = np.random.default_rng(2)
    vals = jnp.asarray(rng.integers(0, cfg.n_values, (5, 3)))
    qs = lvrf.encode_row(atoms, vals, cfg)
    # mix in junk so slots free up at very different times
    junk = jnp.asarray(rng.normal(size=(2, cfg.vsa.dim)), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(42), 5)
    eng = engine.Engine(spec, slots=2, sweeps_per_step=3)
    ids = [eng.submit(qs[i], keys=keys[i][None]) for i in range(5)]
    for i in range(2):
        eng.submit(junk[i])
    done = {r.id: r for r in eng.drain()}
    for i in range(5):
        solo = fz.factorize(qs[i], spec.codebooks, keys[i], spec.cfg,
                            spec.valid_mask)
        req = done[ids[i]]
        assert int(req.iterations[0]) == int(solo.iterations)
        np.testing.assert_array_equal(req.factorization.indices[0],
                                      np.asarray(solo.indices))
        np.testing.assert_allclose(req.factorization.reconstruction_sim[0],
                                   float(solo.reconstruction_sim), rtol=1e-6)


@pytest.mark.parametrize("as_device", [False, True], ids=["numpy", "jax"])
@pytest.mark.parametrize("pipeline", ["nvsa_abduction", "lvrf_rows"])
def test_host_rows_retire_bit_equal_solo(pipeline, as_device):
    """Rows kept on the host from submit to fill retire with the solo
    factorize(q, key) trajectory, whether submitted as numpy or jax.Array,
    for both registered factorizer workloads.  Scores match to float32
    rounding only: on the CPU a batch of one and the slot batch reduce in
    another order (~2e-5 relative)."""
    spec = engine.registry.build(pipeline, jax.random.PRNGKey(0))
    n_q = 5
    rng = np.random.default_rng(3)
    F, M = spec.codebooks.shape[:2]
    sizes = (np.full(F, M) if spec.valid_mask is None
             else np.asarray(spec.valid_mask).sum(-1))
    attrs = jnp.asarray(rng.integers(0, sizes, (n_q, F)))
    qs = np.asarray(fz.bind_combo(spec.codebooks, attrs, spec.cfg.vsa))
    qs = (qs + 0.4 * qs.std() * rng.normal(size=qs.shape)).astype(np.float32)
    keys = jax.random.split(jax.random.PRNGKey(9), n_q)
    if not as_device:
        keys = np.asarray(keys)
    eng = engine.Engine(spec, slots=3, sweeps_per_step=2)
    ids = [eng.submit(jnp.asarray(qs[i]) if as_device else qs[i],
                      keys=keys[i:i + 1]) for i in range(n_q)]
    done = {r.id: r for r in eng.drain()}
    for i, rid in enumerate(ids):
        got = done[rid].factorization
        solo = fz.factorize(jnp.asarray(qs[i]), spec.codebooks,
                            jnp.asarray(keys[i]), spec.cfg, spec.valid_mask)
        np.testing.assert_array_equal(got.indices[0], np.asarray(solo.indices))
        assert int(got.iterations[0]) == int(solo.iterations)
        assert bool(got.converged[0]) == bool(solo.converged)
        np.testing.assert_allclose(got.scores[0], np.asarray(solo.scores),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("keys_from", ["caller", "engine"])
@pytest.mark.parametrize("as_device", [False, True], ids=["numpy", "jax"])
def test_submit_keeps_rows_on_host(lvrf_setup, as_device, keys_from):
    """A request's queries and keys are host arrays from submit on, whether
    the caller sent numpy or jax.Array and whether the engine derived the
    keys itself."""
    spec, _, _ = lvrf_setup
    qs = np.random.default_rng(0).normal(size=(3, spec.dim))
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    eng = engine.Engine(spec, slots=4, sweeps_per_step=1)
    kw = {"keys": keys if as_device else np.asarray(keys)} \
        if keys_from == "caller" else {}
    eng.submit(jnp.asarray(qs) if as_device else qs, **kw)
    req = eng._queue[0][0]
    assert type(req.queries) is np.ndarray and type(req.keys) is np.ndarray
    assert req.queries.dtype == np.float32 and req.queries.shape == (3,
                                                                     spec.dim)
    assert req.keys.shape == (3, 2)
    if keys_from == "caller":
        np.testing.assert_array_equal(req.keys, np.asarray(keys))


def test_fill_makes_no_device_op_per_row(lvrf_setup, monkeypatch):
    """One fill of 100 host rows into 128 slots makes no device slice and no
    device pull, and exactly one refill call."""
    spec, _, _ = lvrf_setup
    eng = engine.Engine(spec, slots=128, sweeps_per_step=1)
    rows = np.random.default_rng(0).normal(size=(101, spec.dim))
    eng.submit(rows[:1])
    eng._fill()  # compiles the refill program outside the counted fill
    eng.submit(rows[1:])
    refills = []
    refill = eng._refill_many
    eng._refill_many = lambda *a: refills.append(a) or refill(*a)
    array_t = type(eng.qs)
    calls = {"__getitem__": 0, "__array__": 0, "__buffer__": 0}

    def counted(name):
        orig = getattr(array_t, name)

        def f(self, *a, **k):
            calls[name] += 1
            return orig(self, *a, **k)
        return f

    for name in calls:
        monkeypatch.setattr(array_t, name, counted(name))
    eng._fill()
    monkeypatch.undo()
    assert calls == {"__getitem__": 0, "__array__": 0, "__buffer__": 0}
    assert len(refills) == 1
    assert sum(o is not None for o in eng._owner) == 101
    np.testing.assert_array_equal(np.asarray(eng.qs)[1:101],
                                  rows[1:].astype(np.float32))


def test_sweeps_per_step_is_scheduler_derived(lvrf_setup):
    spec, _, _ = lvrf_setup
    k = engine.derive_sweeps_per_step(spec, slots=16)
    assert isinstance(k, int) and k >= 1
    eng = engine.Engine(spec, slots=16)
    assert eng.sweeps_per_step == k
    assert engine.Engine(spec, slots=16, sweeps_per_step=5).sweeps_per_step == 5


def test_engine_latency_accounting(lvrf_setup):
    spec, cfg, atoms = lvrf_setup
    vals = jnp.asarray(np.random.default_rng(3).integers(0, cfg.n_values, (3, 3)))
    qs = lvrf.encode_row(atoms, vals, cfg)
    eng = engine.Engine(spec, slots=4)
    for i in range(3):
        eng.submit(qs[i])
    done = eng.drain()
    for r in done:
        assert r.latency_s is not None and r.latency_s >= 0
        assert r.done_sweep >= r.submit_sweep
    st = eng.stats()
    assert st["completed"] == 3 and st["latency_p50_ms"] is not None


# ---------------------------------------------------------------------------
# Fused serving: the Pallas sweep behind Engine.submit/step/drain
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lvrf_fused_setup():
    """lvrf_rows compiled for the fused kernel (Jacobi) plus the matching
    UNFUSED Jacobi spec — same key, same codebooks, same algorithm; the only
    difference is where the sweep runs."""
    spec_f = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0),
                                   fused_step=True)
    spec_u = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0),
                                   synchronous=True)
    cfg = lvrf.LVRFConfig()
    atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(0))[0], cfg)
    return spec_f, spec_u, cfg, atoms


def _serve_traj(spec, qs, keys, *, slots=4, resizes=()):
    """Serve every query (pinned keys), optionally resizing mid-run; return
    per-request (indices, iterations, sim, scores) plus the engine."""
    eng = engine.Engine(spec, slots=slots, sweeps_per_step=3)
    ids = [eng.submit(qs[i], keys=keys[i][None]) for i in range(qs.shape[0])]
    fin = list(eng.step())
    for s in resizes:
        eng.resize(s)
        fin += eng.step()
    fin += eng.drain()
    done = {r.id: r for r in fin}
    reqs = [done[i] for i in ids]
    return [(np.asarray(r.factorization.indices),
             np.asarray(r.iterations),
             np.asarray(r.factorization.reconstruction_sim),
             np.asarray(r.factorization.scores)) for r in reqs], eng


def test_fused_engine_bit_equals_unfused_and_solo(lvrf_fused_setup):
    """Acceptance bar (single device): Engine with fused_step=True serves
    bit-identical request trajectories to the unfused Jacobi path, and every
    row reproduces its solo factorize() exactly."""
    spec_f, spec_u, cfg, atoms = lvrf_fused_setup
    assert fz.fused_sweep_eligible(spec_f.cfg)
    assert not fz.fused_sweep_eligible(spec_u.cfg)
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.integers(0, cfg.n_values, (8, 3)))
    qs = lvrf.encode_row(atoms, vals, cfg)
    keys = jax.random.split(jax.random.PRNGKey(42), 8)
    got_f, eng_f = _serve_traj(spec_f, qs, keys)
    got_u, eng_u = _serve_traj(spec_u, qs, keys)
    for tf, tu in zip(got_f, got_u):
        for a, b in zip(tf, tu):
            np.testing.assert_array_equal(a, b)
    assert eng_f.sweeps_total == eng_u.sweeps_total
    for i in range(8):  # fused solo runs agree too (shared sweep closures)
        solo = fz.factorize(qs[i], spec_f.codebooks, keys[i], spec_f.cfg,
                            spec_f.valid_mask)
        np.testing.assert_array_equal(got_f[i][0][0], np.asarray(solo.indices))
        assert int(got_f[i][1][0]) == int(solo.iterations)
    # an explicit FusedConfig (smaller row-tile ceiling) threads through and
    # changes nothing about the math
    eng_t = engine.Engine(spec_f, slots=4, sweeps_per_step=3,
                          fused=engine.FusedConfig(tn=8))
    ids = [eng_t.submit(qs[i], keys=keys[i][None]) for i in range(8)]
    done = {r.id: r for r in eng_t.drain()}
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(
            np.asarray(done[rid].factorization.indices), got_f[i][0])
        np.testing.assert_array_equal(np.asarray(done[rid].iterations),
                                      got_f[i][1])


def test_fused_engine_survives_mid_run_resize(lvrf_fused_setup):
    """Warm-handoff resize THROUGH the fused kernel, including degenerate
    slot counts (6 and 2 — not multiples of the 8-row MXU tile, so the
    shrink exercises the pad-rows guard): trajectories stay bit-equal to
    solo factorize() and to the unfused engine run with the same resizes."""
    spec_f, spec_u, cfg, atoms = lvrf_fused_setup
    rng = np.random.default_rng(4)
    vals = jnp.asarray(rng.integers(0, cfg.n_values, (7, 3)))
    good = lvrf.encode_row(atoms, vals, cfg)
    junk = jnp.asarray(rng.normal(size=(3, cfg.vsa.dim)), jnp.float32)
    qs = jnp.concatenate([good, junk])
    keys = jax.random.split(jax.random.PRNGKey(7), 10)
    got_f, eng_f = _serve_traj(spec_f, qs, keys, slots=8, resizes=(6, 2, 8))
    got_u, _ = _serve_traj(spec_u, qs, keys, slots=8, resizes=(6, 2, 8))
    assert eng_f.resizes_total == 3
    for i in range(7):  # junk rows' scores are trajectory-noise; check good
        for a, b in zip(got_f[i], got_u[i]):
            np.testing.assert_array_equal(a, b)
        solo = fz.factorize(good[i], spec_f.codebooks, keys[i], spec_f.cfg,
                            spec_f.valid_mask)
        np.testing.assert_array_equal(got_f[i][0][0], np.asarray(solo.indices))
        assert int(got_f[i][1][0]) == int(solo.iterations)


def test_nvsa_fused_flag_is_safe_noop_for_unitary():
    """nvsa_abduction with fused_step=True: the default config is unitary +
    stochastic, so fused_sweep_eligible is False and serving falls back to
    the two-pass sweep — results identical to the plain spec."""
    spec_f = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(0),
                                   fused_step=True)
    spec_p = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(0))
    assert spec_f.cfg.fused_step and not spec_p.cfg.fused_step
    assert not fz.fused_sweep_eligible(spec_f.cfg)
    attrs = jnp.asarray(np.random.default_rng(0).integers(0, (5, 6, 10), (2, 3)))
    qs = fz.bind_combo(spec_f.codebooks, attrs, spec_f.cfg.vsa)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    for spec in (spec_f, spec_p):
        eng = engine.Engine(spec, slots=2, sweeps_per_step=4)
        ids = [eng.submit(qs[i], keys=keys[i][None]) for i in range(2)]
        done = {r.id: r for r in eng.drain()}
        np.testing.assert_array_equal(
            np.stack([np.asarray(done[i].factorization.indices[0])
                      for i in ids]),
            np.asarray(attrs))


def test_engine_rejects_bool_fused_kwarg(lvrf_fused_setup):
    """fused= takes a FusedConfig; the natural misuse fused=True (confusing
    it with the spec-level fused_step flag) must fail fast at construction
    with a usable message, not as an AttributeError inside a jit trace."""
    spec_f, _, _, _ = lvrf_fused_setup
    with pytest.raises(TypeError, match="FusedConfig"):
        engine.Engine(spec_f, slots=4, fused=True)
    from repro.kernels.resonator_step import ops as rs_ops
    with pytest.raises(TypeError, match="FusedConfig"):
        rs_ops._cfg(True)
