"""Sharded serving subsystem tests.

Mesh-level parity runs in subprocesses with 8 fake host devices (same
pattern as test_distributed.py) so the tier-1 single-device run still
collects and passes everything; the cost-model / autotune / clamping tests
run in-process with however many devices exist.

Parity contract (see repro/engine/sharding/engine.py):
  * ``codebook_placement="replicated"`` — bit-identical to the
    single-device Engine for every workload (all sweep math is row-local);
  * ``codebook_placement="rows"`` — bit-identical for bipolar codebooks
    with elementwise activations (lvrf: the packed psum adds integers,
    which is associative in fp32), trajectory-identical with last-ulp
    `scores` drift for real algebras (nvsa: the projection psum
    reassociates the fp row-sum).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core import factorizer as fz
from repro.core import scheduler as sch
from repro.core.scheduler import Op
from repro.engine import registry, sharding
from repro.engine.build import plan_interleave
from repro.engine.sharding import choose_slots, shard_graph, shard_ops
from repro.engine.stage import Stage, StageGraph
from repro.launch import mesh as launch_mesh

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_with_devices(code: str, n: int = 8) -> dict:
    """Run `code` in a subprocess with n fake devices; it must print JSON."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# Mesh parity: ShardedEngine == Engine on a 4x2 host mesh
# ---------------------------------------------------------------------------

def test_sharded_engine_bit_equals_engine_lvrf_both_placements():
    """10 requests (incl. never-converging junk exercising cross-shard slot
    recycling) served by Engine and by ShardedEngine on a 4x2 mesh under
    both codebook placements: trajectories must agree bit for bit, and the
    rows placement must also agree on solo factorize() calls."""
    r = run_with_devices(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro import engine
        from repro.core import factorizer as fz
        from repro.launch.mesh import make_host_mesh
        from repro.models import lvrf

        spec = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0))
        cfg = lvrf.LVRFConfig()
        atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(0))[0], cfg)
        rng = np.random.default_rng(0)
        vals = jnp.asarray(rng.integers(0, cfg.n_values, (8, 3)))
        good = lvrf.encode_row(atoms, vals, cfg)
        junk = jnp.asarray(rng.normal(size=(2, cfg.vsa.dim)), jnp.float32)
        qs = jnp.concatenate([good, junk])
        keys = jax.random.split(jax.random.PRNGKey(42), 10)

        def serve(eng):
            ids = [eng.submit(qs[i], keys=keys[i][None]) for i in range(10)]
            done = {r.id: r for r in eng.drain()}
            return [done[i] for i in ids], eng.sweeps_total

        def fields(reqs):
            # scores compared for the 8 real workload rows only: junk rows
            # are real-valued, so XLA's CPU dot (1-row-per-shard gemv vs
            # 4-row gemm) accumulates in a different order, and over 40
            # non-converging sweeps the ulp drift flips near-zero sign()
            # bits in their (meaningless) estimates.  idx/iterations/sim —
            # the serving contract — are still checked for every row.
            return {
                "idx": [np.asarray(r.factorization.indices).tolist() for r in reqs],
                "it": [np.asarray(r.iterations).tolist() for r in reqs],
                "sim": [np.asarray(r.factorization.reconstruction_sim).tolist() for r in reqs],
                "sc": [np.asarray(r.factorization.scores).tolist() for r in reqs[:8]],
            }

        base, base_sweeps = serve(engine.Engine(spec, slots=4, sweeps_per_step=3))
        want = fields(base)
        mesh = make_host_mesh(4, 2)
        out = {"mesh": list(mesh.devices.shape)}
        for placement in ("replicated", "rows"):
            got, sweeps = serve(engine.ShardedEngine(
                spec, mesh=mesh, codebook_placement=placement, slots=4,
                sweeps_per_step=3))
            g = fields(got)
            out[placement] = {k: g[k] == want[k] for k in want}
            out[placement]["sweeps_equal"] = sweeps == base_sweeps
        solo = fz.factorize(qs[0], spec.codebooks, keys[0], spec.cfg)
        out["solo_iters"] = int(solo.iterations)
        out["req0_iters"] = int(base[0].iterations[0])
        print(json.dumps(out))
    """))
    assert r["mesh"] == [4, 2]
    for placement in ("replicated", "rows"):
        assert all(r[placement].values()), (placement, r[placement])
    # engine rows reproduce solo factorize trajectories (slot independence)
    assert r["solo_iters"] == r["req0_iters"]


def test_sharded_resize_warm_handoff_on_mesh():
    """Online re-tune on the mesh: grow 8->16 and shrink ->4 global slots
    mid-flight (junk rows in flight both times); every request stays
    bit-equal to a solo factorize(), and an invalid slot count (not a
    multiple of the data axis) is rejected."""
    r = run_with_devices(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro import engine
        from repro.core import factorizer as fz
        from repro.launch.mesh import make_host_mesh
        from repro.models import lvrf

        spec = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0))
        cfg = lvrf.LVRFConfig()
        atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(0))[0], cfg)
        rng = np.random.default_rng(1)
        vals = jnp.asarray(rng.integers(0, cfg.n_values, (8, 3)))
        good = lvrf.encode_row(atoms, vals, cfg)
        junk = jnp.asarray(rng.normal(size=(4, cfg.vsa.dim)), jnp.float32)
        keys = jax.random.split(jax.random.PRNGKey(7), 8)

        mesh = make_host_mesh(4, 2)
        eng = engine.ShardedEngine(spec, mesh=mesh, slots=8, sweeps_per_step=2)
        ids = [eng.submit(good[i], keys=keys[i][None]) for i in range(8)]
        for j in range(4):
            eng.submit(junk[j])
        fin = list(eng.step())
        eng.resize(16)
        fin += eng.step()
        bad = False
        try:
            eng.resize(6)
        except ValueError:
            bad = True
        eng.resize(4)
        fin += eng.drain()
        done = {r.id: r for r in fin}
        ok = True
        for i in range(8):
            solo = fz.factorize(good[i], spec.codebooks, keys[i], spec.cfg,
                                spec.valid_mask)
            req = done[ids[i]]
            ok &= int(req.iterations[0]) == int(solo.iterations)
            ok &= bool((np.asarray(req.factorization.indices[0])
                        == np.asarray(solo.indices)).all())
        print(json.dumps({"ok": ok, "bad_rejected": bad,
                          "resizes": eng.resizes_total,
                          "completed": len(done)}))
    """))
    assert r["ok"] and r["bad_rejected"]
    assert r["resizes"] == 2 and r["completed"] == 12


def test_sharded_engine_nvsa_4x2_mesh():
    """NVSA abduction through ShardedEngine on 4x2: replicated placement is
    bit-identical to nvsa.solve (like the single-device engine test); rows
    placement keeps the answer/iteration trajectory with allclose sims."""
    r = run_with_devices(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro import engine
        from repro.launch.mesh import make_host_mesh
        from repro.models import cnn, nvsa

        cfg = nvsa.NVSAConfig()
        cbs, mask = nvsa.make_codebooks(jax.random.PRNGKey(0), cfg)
        params = cnn.init(jax.random.PRNGKey(1), cfg.cnn)
        batch = {"images": jax.random.uniform(jax.random.PRNGKey(2), (1, 9, 32, 32)),
                 "candidate_images": jax.random.uniform(jax.random.PRNGKey(3),
                                                        (1, 8, 32, 32))}
        key = jax.random.PRNGKey(11)
        want = nvsa.solve(params, batch, cbs, mask, key, cfg)
        ctx = nvsa.perceive(params, batch["images"][:, :8], cfg, cbs)[0]
        cand = nvsa.perceive(params, batch["candidate_images"], cfg, cbs)[0]
        qkeys = jax.random.split(jax.random.split(key)[0], 8)
        spec = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(0),
                                     cfg=cfg, params=params, batch=1)
        mesh = make_host_mesh(4, 2)
        out = {}
        for placement in ("replicated", "rows"):
            eng = engine.ShardedEngine(spec, mesh=mesh,
                                       codebook_placement=placement, slots=8)
            eng.submit(ctx, keys=qkeys, meta={"cand": cand})
            (req,) = eng.drain()
            out[placement] = {
                "answer": req.result["answer"] == int(want["answer"][0]),
                "iters": np.array_equal(np.asarray(req.iterations),
                                        np.asarray(want["fact_iters"][0])),
                "sims": bool(np.allclose(np.asarray(req.result["sims"]),
                                         np.asarray(want["sims"][0]),
                                         rtol=1e-5)),
            }
        print(json.dumps(out))
    """))
    for placement in ("replicated", "rows"):
        assert all(r[placement].values()), (placement, r[placement])


def test_sharded_sweep_jaxpr_has_one_psum_per_scored_row():
    """The rows-placement sweep must issue exactly ONE packed psum per
    scored codebook row (factor) — carrying the zero-padded local scores
    and the partial projection together — plus the single one-hot psum that
    gathers the F decoded atom rows for the convergence check.  More psums
    than F+1 means the packing regressed into separate score/projection
    collectives; fewer means a collective was silently elided."""
    r = run_with_devices(textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro import engine
        from repro.core import factorizer as fz
        from repro.launch.mesh import make_host_mesh
        from repro.launch.programs import primitive_names

        spec = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0))
        cfg, cb = spec.cfg, spec.codebooks
        F, M, D = cb.shape
        mesh = make_host_mesh(4, 2)
        init_est = fz.superposition_init(cb, cfg)
        n_loc = 2

        def one_sweep(cb_loc, qs, st):
            rs = fz.make_resonator(cb_loc, cfg, None, model_axis="model",
                                   full_rows=M, init_est=init_est)
            return rs.sweep(qs, st)

        qs = jnp.zeros((8, D), jnp.float32)
        rs0 = fz.make_resonator(cb, cfg, None)
        st = rs0.init(qs, jax.random.split(jax.random.PRNGKey(0), 8))
        state_spec = type(st)(*([P("data")] * 5 + [P()]))
        f = jax.shard_map(one_sweep, mesh=mesh,
                          in_specs=(P(None, "model", None), P("data"),
                                    state_spec),
                          out_specs=state_spec, check_vma=False)

        names = primitive_names(jax.make_jaxpr(f)(cb, qs, st))
        print(json.dumps({"psums": names.count("psum"), "F": int(F)}))
    """))
    assert r["psums"] == r["F"] + 1, r


# ---------------------------------------------------------------------------
# Fused serving on the mesh (mask-aware / shard-aware kernel variants)
# ---------------------------------------------------------------------------

def test_sharded_engine_fused_bit_equals_unfused_both_placements():
    """Acceptance bar: ShardedEngine serves lvrf_rows with fused_step=True —
    replicated placement runs the fused kernel per data shard (local row
    counts down to n_loc=1, the degenerate-N regime), rows placement runs
    the shard-aware kernel with one packed psum per factor — and every
    trajectory is bit-identical to BOTH the single-device fused Engine and
    the single-device UNFUSED Jacobi engine."""
    r = run_with_devices(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro import engine
        from repro.launch.mesh import make_host_mesh
        from repro.models import lvrf

        spec_f = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0),
                                       fused_step=True)
        spec_u = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0),
                                       synchronous=True)
        cfg = lvrf.LVRFConfig()
        atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(0))[0], cfg)
        rng = np.random.default_rng(0)
        vals = jnp.asarray(rng.integers(0, cfg.n_values, (8, 3)))
        qs = lvrf.encode_row(atoms, vals, cfg)
        keys = jax.random.split(jax.random.PRNGKey(42), 8)

        def serve(eng):
            ids = [eng.submit(qs[i], keys=keys[i][None]) for i in range(8)]
            done = {r.id: r for r in eng.drain()}
            return [done[i] for i in ids], eng.sweeps_total

        def fields(reqs):
            return {
                "idx": [np.asarray(r.factorization.indices).tolist() for r in reqs],
                "it": [np.asarray(r.iterations).tolist() for r in reqs],
                "sim": [np.asarray(r.factorization.reconstruction_sim).tolist() for r in reqs],
                "sc": [np.asarray(r.factorization.scores).tolist() for r in reqs],
            }

        base, base_sweeps = serve(engine.Engine(spec_f, slots=4,
                                                sweeps_per_step=3))
        want = fields(base)
        unf, unf_sweeps = serve(engine.Engine(spec_u, slots=4,
                                              sweeps_per_step=3))
        out = {"fused_equals_unfused": fields(unf) == want
                                       and unf_sweeps == base_sweeps}
        mesh = make_host_mesh(4, 2)
        for placement in ("replicated", "rows"):
            got, sweeps = serve(engine.ShardedEngine(
                spec_f, mesh=mesh, codebook_placement=placement, slots=4,
                sweeps_per_step=3))
            g = fields(got)
            out[placement] = {k: g[k] == want[k] for k in want}
            out[placement]["sweeps_equal"] = sweeps == base_sweeps
        print(json.dumps(out))
    """))
    assert r["fused_equals_unfused"]
    for placement in ("replicated", "rows"):
        assert all(r[placement].values()), (placement, r[placement])


def test_sharded_fused_sweep_jaxpr_has_one_psum_per_factor():
    """The rows-sharded FUSED sweep must keep the unfused path's collective
    contract: exactly F packed psums (zero-padded local scores + partial
    projection per factor, produced by the shard-aware kernel) plus the
    one-hot convergence gather — F+1 total, with the sweep itself lowered to
    ONE pallas_call."""
    r = run_with_devices(textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro import engine
        from repro.core import factorizer as fz
        from repro.launch.mesh import make_host_mesh
        from repro.launch.programs import primitive_names

        spec = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0),
                                     fused_step=True)
        cfg, cb = spec.cfg, spec.codebooks
        F, M, D = cb.shape
        mesh = make_host_mesh(4, 2)
        init_est = fz.superposition_init(cb, cfg)

        def one_sweep(cb_loc, qs, st):
            rs = fz.make_resonator(cb_loc, cfg, None, model_axis="model",
                                   full_rows=M, init_est=init_est)
            return rs.sweep(qs, st)

        qs = jnp.zeros((8, D), jnp.float32)
        rs0 = fz.make_resonator(cb, cfg, None)
        st = rs0.init(qs, jax.random.split(jax.random.PRNGKey(0), 8))
        state_spec = type(st)(*([P("data")] * 5 + [P()]))
        f = jax.shard_map(one_sweep, mesh=mesh,
                          in_specs=(P(None, "model", None), P("data"),
                                    state_spec),
                          out_specs=state_spec, check_vma=False)

        names = primitive_names(jax.make_jaxpr(f)(cb, qs, st))
        print(json.dumps({"psums": names.count("psum"), "F": int(F),
                          "pallas_calls": names.count("pallas_call")}))
    """))
    assert r["psums"] == r["F"] + 1, r
    assert r["pallas_calls"] == 1, r


# ---------------------------------------------------------------------------
# Collective-aware scheduling (no mesh needed)
# ---------------------------------------------------------------------------

def test_collective_op_cycles_match_ici_model():
    from repro.cogsim.model import COGSYS

    nbytes, p = 4 * 32 * (10 + 2048), 4
    op = Op("ps", "collective", (nbytes, p), collective="psum")
    want = launch_mesh.collective_seconds(nbytes, p, "psum") * COGSYS.freq_hz
    assert sch.op_cycles(op, COGSYS, 0) == pytest.approx(want)
    assert op.flops() == 0.0
    assert op.bytes_moved() == float(nbytes)
    # all_gather moves half a psum's wire traffic
    ag = launch_mesh.collective_seconds(nbytes, p, "all_gather")
    ps = launch_mesh.collective_seconds(nbytes, p, "psum")
    assert ps - launch_mesh.ICI_LATENCY_S == \
        pytest.approx(2 * (ag - launch_mesh.ICI_LATENCY_S))
    assert launch_mesh.collective_seconds(nbytes, 1) == 0.0


def test_schedule_places_collectives_off_the_cell_pool():
    """A collective op schedules like a SIMD op — no cells grabbed — and its
    duration lands in the makespan."""
    from repro.cogsim.model import COGSYS

    ops = [Op("g", "gemm", (256, 256, 256), symbolic=True),
           Op("ps", "collective", (1 << 20, 4), deps=("g",), symbolic=True)]
    s = sch.schedule(ops, COGSYS)
    sch.validate(s, ops)
    by_name = {p.op.name: p for p in s.placements}
    assert by_name["ps"].cells == ()
    assert s.makespan >= by_name["g"].end + sch.op_cycles(ops[1], COGSYS, 0)


def test_sweep_cost_ops_sharded_dims_and_collectives():
    cfg = fz.FactorizerConfig(vsa=__import__("repro.core.vsa", fromlist=["VSAConfig"]).VSAConfig(1024, 1024),
                              num_factors=3, codebook_size=16)
    dense = {o.name: o for o in fz.sweep_cost_ops(cfg, 64)}
    assert not any(o.kind == "collective" for o in dense.values())
    shard = {o.name: o for o in fz.sweep_cost_ops(cfg, 64, data_shards=4,
                                                  model_shards=2)}
    assert shard["scores"].dims == (16 * 3, 1024, 8)  # rows/4, cols M/2
    assert shard["psum_scores"].kind == "collective"
    assert shard["psum_scores"].dims[1] == 2
    assert shard["converge"].deps == ("psum_recon",)
    assert dense["converge"].deps == ("project",)


def test_shard_graph_prices_collectives_into_the_plan():
    """shard_graph rescales dims per shard and appends a psum after every
    symbolic gemm, rewiring deps through it; plan_interleave(shards=) then
    schedules wire time instead of free communication."""
    g = StageGraph("toy", (
        Stage("n", None, symbolic=False,
              cost_ops=(Op("g1", "gemm", (4096, 512, 512)),)),
        Stage("s", None, symbolic=True,
              cost_ops=(Op("score", "gemm", (512, 1024, 32), symbolic=True),
                        Op("norm", "simd", (512 * 1024,), deps=("score",),
                           symbolic=True))),
    ))
    sg = shard_graph(g, 4, 2)
    ops = {o.name: o for st in sg.stages for o in st.cost_ops}
    assert ops["g1"].dims == (1024, 512, 512)  # data-sharded, no collective
    assert ops["score_psum"].kind == "collective"
    assert ops["score_psum"].dims == (4.0 * 128 * 32, 2)
    assert ops["norm"].deps == ("score_psum",)  # rewired through the gather
    plan = plan_interleave(g, shards=(4, 2))
    assert plan.makespan_overlap > 0
    # pure data sharding adds no collectives
    assert not any(o.kind == "collective" for st in shard_graph(g, 4, 1).stages
                   for o in st.cost_ops)


def test_sweep_cost_ops_fused_flag_halves_codebook_hbm():
    """fused marks the projection gemm weight_resident: its codebook HBM
    term (k*n bytes) disappears while flops are unchanged, and the default
    flag follows the config's own fused-sweep eligibility."""
    from repro.core import vsa as vsa_mod

    cfg = fz.FactorizerConfig(vsa=vsa_mod.VSAConfig(1024, 1024),
                              num_factors=3, codebook_size=16,
                              algebra="bipolar")
    two_pass = {o.name: o for o in fz.sweep_cost_ops(cfg, 64)}
    fused = {o.name: o for o in fz.sweep_cost_ops(cfg, 64, fused=True)}
    assert not two_pass["project"].weight_resident
    assert fused["project"].weight_resident
    m, k, n = fused["project"].dims
    assert two_pass["project"].bytes_moved() - fused["project"].bytes_moved() \
        == k * n  # exactly the codebook read
    assert fused["project"].flops() == two_pass["project"].flops()
    assert fused["scores"].bytes_moved() == two_pass["scores"].bytes_moved()
    # default flag = fused_sweep_eligible(cfg)
    import dataclasses as dc
    cfg_f = dc.replace(cfg, fused_step=True, synchronous=True)
    auto = {o.name: o for o in fz.sweep_cost_ops(cfg_f, 64)}
    assert auto["project"].weight_resident
    assert fz.fused_sweep_eligible(cfg_f)
    assert not fz.fused_sweep_eligible(dc.replace(cfg_f, noise_std=0.3))
    # ...and choose_slots prices the fused path as (weakly) cheaper
    t_two = sharding.autotune.modeled_sweep_seconds(cfg, 64, fused=False)
    t_fused = sharding.autotune.modeled_sweep_seconds(cfg, 64, fused=True)
    assert t_fused <= t_two


def test_shard_graph_packs_fused_pair_into_one_psum():
    """A weight_resident gemm consuming another gemm is a fused pair: under
    model sharding the pair gathers with ONE packed psum carrying both
    outputs (the fused sharded sweep's contract), not two collectives."""
    from repro.engine.sharding.costs import mark_fused

    g = StageGraph("toy", (
        Stage("s", None, symbolic=True,
              cost_ops=(Op("score", "gemm", (64, 1024, 16), symbolic=True),
                        Op("project", "gemm", (64, 16, 1024),
                           deps=("score",), symbolic=True),
                        Op("conv", "simd", (64,), deps=("project",),
                           symbolic=True))),
    ))
    # two-pass: one psum per gemm
    ops = {o.name: o for st in shard_graph(g, 1, 2).stages
           for o in st.cost_ops}
    assert "score_psum" in ops and "project_psum" in ops
    # fused: the score's gather rides the pair's packed psum
    gf = mark_fused(g)
    ops_f = [o for st in shard_graph(gf, 1, 2).stages for o in st.cost_ops]
    by_name = {o.name: o for o in ops_f}
    assert "score_psum" not in by_name
    packed = by_name["project_psum"]
    assert packed.dims[0] == 4.0 * (64 * 16 + 64 * 1024)  # both outputs
    assert by_name["conv"].deps == ("project_psum",)
    assert sum(o.kind == "collective" for o in ops_f) == 1
    # mark_fused(False) restores two-pass pricing
    ops_u = {o.name: o for st in shard_graph(mark_fused(gf, False), 1, 2).stages
             for o in st.cost_ops}
    assert "score_psum" in ops_u and not ops_u["project"].weight_resident
    # declaration order must not matter (cost_ops are hand-declared tuples),
    # and a THIRD-PARTY consumer of the producer must wait on the packed
    # gather while the pair's own edge stays raw
    g_rev = StageGraph("rev", (
        Stage("s", None, symbolic=True,
              cost_ops=(Op("project", "gemm", (64, 16, 1024),
                           deps=("score",), symbolic=True,
                           weight_resident=True),
                        Op("score", "gemm", (64, 1024, 16), symbolic=True),
                        Op("argmax", "simd", (64 * 16,), deps=("score",),
                           symbolic=True))),
    ))
    ops_r = {o.name: o for st in shard_graph(g_rev, 1, 2).stages
             for o in st.cost_ops}
    assert "score_psum" not in ops_r
    assert ops_r["project_psum"].dims[0] == 4.0 * (64 * 16 + 64 * 1024)
    assert ops_r["project"].deps == ("score",)  # pair edge stays raw
    assert ops_r["argmax"].deps == ("project_psum",)  # third party waits
    # a weight-resident CHAIN must not silently drop gathers: only the last
    # pair packs; upstream gemms keep their own psums, and a third-party
    # consumer of the head gemm waits on the head's gather
    g_chain = StageGraph("chain", (
        Stage("s", None, symbolic=True,
              cost_ops=(Op("g1", "gemm", (64, 512, 32), symbolic=True),
                        Op("g2", "gemm", (64, 32, 512), deps=("g1",),
                           symbolic=True, weight_resident=True),
                        Op("g3", "gemm", (64, 512, 32), deps=("g2",),
                           symbolic=True, weight_resident=True),
                        Op("use_g1", "simd", (64,), deps=("g1",),
                           symbolic=True))),
    ))
    ops_c = {o.name: o for st in shard_graph(g_chain, 1, 2).stages
             for o in st.cost_ops}
    assert "g1_psum" in ops_c  # head gather NOT dropped
    assert "g2_psum" not in ops_c  # middle rides the last pair's psum
    assert ops_c["g3_psum"].dims[0] == 4.0 * (64 * 32 + 64 * 512)
    assert ops_c["use_g1"].deps == ("g1_psum",)
    assert ops_c["g2"].deps == ("g1_psum",)  # g1/g2 are NOT a packed pair
    # plan_interleave threads the override end to end
    g2 = StageGraph("toy2", (
        Stage("n", None, symbolic=False,
              cost_ops=(Op("g1", "gemm", (4096, 512, 512)),)),) + g.stages)
    plan_f = plan_interleave(g2, shards=(1, 2), fused=True)
    plan_u = plan_interleave(g2, shards=(1, 2), fused=False)
    assert plan_f.makespan_overlap <= plan_u.makespan_overlap


def test_retune_slots_measured_step_unit_is_wall_clock_basis():
    """The unit-mismatch fix: analytic adSCH rates are modeled
    device-seconds (orders of magnitude below wall cost), so an analytic
    re-tune at a moderate wall-clock arrival rate never moves slots; a
    measured wall-clock step cost at the SAME arrival rate does."""
    spec = registry.build("lvrf_rows", jax.random.PRNGKey(0))
    from repro.engine import Engine
    from repro.engine.sharding.autotune import retune_slots

    eng = Engine(spec, slots=4, sweeps_per_step=2)
    # analytic: modeled device-second rates dwarf 50 rps -> smallest
    # candidate keeps up -> verdict equals current slots -> no move
    assert retune_slots(eng, 50.0) is None
    # measured: 50 ms wall per sweep at the current 4 slots cannot retire
    # 50 wall-clock requests/s -> the re-tune must move slots up
    verdict = retune_slots(eng, 50.0, measured_step_unit_s=0.05)
    assert verdict is not None and verdict > eng.slots


def test_shard_ops_scales_batch_dims_only():
    ops = [Op("c", "circconv", (120, 256), symbolic=True),
           Op("s", "simd", (1000,)),
           Op("ps", "collective", (4096, 2))]
    out = {o.name: o for o in shard_ops(ops, 8)}
    assert out["c"].dims == (15, 256)
    assert out["s"].dims == (125,)
    assert out["ps"].dims == (4096, 2)  # already per-device


# ---------------------------------------------------------------------------
# choose_slots autotuner
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lvrf_spec():
    return registry.build("lvrf_rows", jax.random.PRNGKey(0))


def test_choose_slots_is_arrival_driven(lvrf_spec):
    lo = choose_slots(lvrf_spec, arrival_rps=1.0)
    hi = choose_slots(lvrf_spec, arrival_rps=1e9)
    assert lo <= hi
    assert lo == min(sharding.autotune.DEFAULT_CANDIDATES)
    assert hi == max(sharding.autotune.DEFAULT_CANDIDATES)
    # monotone over a rate sweep, and always a candidate
    prev = 0
    for rps in (1.0, 1e3, 1e5, 1e7, 1e9):
        n = choose_slots(lvrf_spec, arrival_rps=rps)
        assert n in sharding.autotune.DEFAULT_CANDIDATES
        assert n >= prev
        prev = n


def test_choose_slots_uses_measured_sweep_cost(lvrf_spec):
    calls = []

    def measured(n):
        calls.append(n)
        return 1e-3 * n  # linear cost -> throughput flat -> knee at smallest

    n = choose_slots(lvrf_spec, measured_sweep_s=measured)
    assert calls, "measured sweep cost was never consulted"
    assert n == min(sharding.autotune.DEFAULT_CANDIDATES)
    # with modeled costs the knee sits higher (fill/drain amortisation)
    assert choose_slots(lvrf_spec) > n


def test_choose_slots_scales_service_rate_with_shards(lvrf_spec):
    r1 = sharding.service_rate_rps(lvrf_spec, 32)
    r4 = sharding.service_rate_rps(lvrf_spec, 32, data_shards=4)
    assert r4 > r1  # four shards retire more requests per second
    # a high arrival rate needs fewer slots per shard once sharded
    need1 = choose_slots(lvrf_spec, arrival_rps=0.5 * r1 * 8)
    need4 = choose_slots(lvrf_spec, arrival_rps=0.5 * r1 * 8, data_shards=4)
    assert need4 <= need1


# ---------------------------------------------------------------------------
# make_host_mesh clamping (satellite)
# ---------------------------------------------------------------------------

def test_make_host_mesh_clamps_data_to_device_count():
    n = len(jax.devices())
    mesh = launch_mesh.make_host_mesh(data=1000, model=1)
    assert mesh.shape["data"] == n
    assert mesh.shape["model"] == 1


def test_make_host_mesh_errors_on_oversized_model():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        launch_mesh.make_host_mesh(data=1, model=n + 1)
