"""Chip smoke: drive the serving path once on a TPU and check its answers.

    python chip_smoke.py              # one chip: NVSA, LVRF (fused), LM phases
    python chip_smoke.py --chips 4    # four chips: ShardedEngine phase only

Every phase serves through the normal entry points (``Runtime`` in front of
``Engine`` / ``LMEngine``) at the widths the repo supports, with inputs made
from seeds and weights from ``init`` functions, and compares what it served
with a reference computed in the same process on the same chip:

* ``nvsa``: seeded RAVEN panels -> ``nvsa.perceive`` (seeded CNN) -> query
  vectors served by ``Engine(nvsa_abduction)`` (D=1024, F=3, M=(5,6,10));
  decoded indices and answers must equal ``nvsa.solve`` on the same batch.
* ``lvrf``: ``lvrf_rows(fused_step=True)`` (D=2048, M=10); clean rows must
  decode to their seeded values and match the unfused engine exactly, and
  the sweep program must hold the Pallas kernel (``tpu_custom_call``).
* ``lm``: llama3.2-3b at full width in bf16 through ``LMEngine`` with the
  paged KV pool and flash-decode kernel; first-token logits must agree with
  the contiguous dense-attention path within ``LM_LOGIT_RTOL``.
* ``sharded`` (``--chips 4`` only): ``ShardedEngine`` on a 4x1 mesh with
  replicated codebooks (NVSA) and on a 2x2 mesh with row-sharded codebooks
  and the fused LVRF sweep; decodes must equal a one-chip ``Engine``.

Each phase prints its compile seconds (backend compiles, from JAX's own
monitoring events), its cold and warm serving seconds, the compile-cache
directory, its largest difference from the reference and whether its
compiled program holds ``tpu_custom_call``.  The last line of standard
output is one JSON object; it is printed only when every phase passed.  The
script refuses to run anywhere but a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import engine  # noqa: E402
from repro.configs.registry import ARCHS  # noqa: E402
from repro.core import factorizer as fz  # noqa: E402
from repro.data import raven  # noqa: E402
from repro.kernels import resolve_interpret  # noqa: E402
from repro.launch import programs  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.lm.kv import ContiguousKV, PagedKV  # noqa: E402
from repro.lm.paging import PagedConfig  # noqa: E402
from repro.models import cnn, lvrf, nvsa  # noqa: E402
from repro.nn import transformer as T  # noqa: E402
from repro.runtime import LMEngine, Runtime  # noqa: E402

# Engine postprocess and nvsa.solve compute the candidate similarities from
# the same beliefs; they may differ only by how XLA fuses the two programs.
NVSA_SIMS_ATOL = 1e-3
# LVRF codebooks and queries are +-1, so every score is an exact integer in
# fp32 on both paths: the fused kernel must reproduce them exactly.
LVRF_SCORE_ATOL = 0.0
# Jacobi sweeps (which the fused kernel runs) can cycle on a clean row and
# retire at max_iters without converging (seed 0: 1 row of 64).  Every row
# that converged must decode to its seeded values, and most rows must.
LVRF_MIN_CONVERGED = 0.9
# bf16 weights, activations and KV cache over 28 layers: the paged chunked
# prefill + flash-decode path and the contiguous per-token dense path round
# differently.  Max |logit difference| over max |logit|.
LM_LOGIT_RTOL = 5e-2
RESULT_TIMEOUT_S = 900.0
# A cold full-width compile runs inside the first engine step.
WATCHDOG_S = 900.0


class SmokeFailure(AssertionError):
    """A phase served a wrong answer or a program without its kernel."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's backend-compile durations (its monitoring events)."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def has_kernel(jitted, *args) -> bool:
    return programs.has_tpu_kernel(jitted.lower(*args).compile())


def kernel_expected(on_path: bool) -> bool:
    """A kernel on the path must lower natively (``tpu_custom_call``)
    wherever Pallas compiles; only off-TPU (the CPU tests of these phases)
    does it run interpreted, with no custom call."""
    return on_path and not resolve_interpret()


def serve(runtime: Runtime, submissions) -> list:
    """Submit ``(engine_name, payload, kwargs)`` triples, wait for all."""
    gids = [runtime.submit(name, payload, **kw)
            for name, payload, kw in submissions]
    return [runtime.result(g, timeout=RESULT_TIMEOUT_S) for g in gids]


def timed_twice(runtime: Runtime, submissions) -> tuple:
    """Serve the same submissions twice, cold then warm:
    ``(cold_results, warm_results, cold_s, warm_s)``."""
    t0 = time.perf_counter()
    cold = serve(runtime, submissions)
    t1 = time.perf_counter()
    warm = serve(runtime, submissions)
    t2 = time.perf_counter()
    return cold, warm, t1 - t0, t2 - t1


def _runtime(engines: dict) -> Runtime:
    rt = Runtime(watchdog_s=WATCHDOG_S)
    for name, eng in engines.items():
        rt.register(name, eng)
    return rt


# ---------------------------------------------------------------------------
# Phases: each returns a report dict; a wrong answer raises SmokeFailure
# ---------------------------------------------------------------------------

def nvsa_phase(seed: int, tasks: int = 4) -> dict:
    """Perceive seeded RAVEN tasks, serve the panel queries through Runtime ->
    Engine(nvsa_abduction), compare with nvsa.solve on the same batch."""
    cfg = nvsa.NVSAConfig()
    batch = raven.RavenDataset(
        raven.RavenConfig(batch_size=tasks, seed=seed)).next_batch()
    params = cnn.init(jax.random.PRNGKey(seed), cfg.cnn)
    spec = engine.registry.build("nvsa_abduction",
                                 jax.random.PRNGKey(seed + 1), cfg=cfg,
                                 params=params, batch=tasks)
    cbs, mask = spec.codebooks, spec.valid_mask
    images = jnp.asarray(batch["images"], jnp.float32)
    cand_images = jnp.asarray(batch["candidate_images"], jnp.float32)
    key = jax.random.PRNGKey(seed + 2)
    # nvsa.solve's per-query key layout: split(k1, tasks * 8), task-major
    k1, _ = jax.random.split(key)
    qkeys = jax.random.split(k1, tasks * 8).reshape(tasks, 8, -1)

    perceive = jax.jit(lambda im: nvsa.perceive(params, im, cfg, cbs))
    ctx, cand = perceive(images[:, :8]), perceive(cand_images)
    eng = engine.Engine(spec, slots=8 * tasks)  # solve's batch shape
    subs = [("nvsa", ctx[b], {"keys": qkeys[b], "meta": {"cand": cand[b]}})
            for b in range(tasks)]
    with _runtime({"nvsa": eng}) as rt:
        cold, warm, cold_s, warm_s = timed_twice(rt, subs)

    want = nvsa.solve(params, {"images": images, "candidate_images":
                               cand_images}, cbs, mask, key, cfg)
    _, want_res = nvsa.beliefs_from_queries(
        ctx.reshape(tasks * 8, -1), cbs, mask, k1, cfg)
    want_idx = np.asarray(want_res.indices).reshape(tasks, 8, -1)
    got_idx = np.stack([np.asarray(r.result["indices"]) for r in warm])
    got_ans = np.array([r.result["answer"] for r in warm])
    got_sims = np.stack([np.asarray(r.result["sims"]) for r in warm])
    check(all(np.array_equal(np.asarray(a.result["indices"]),
                             np.asarray(b.result["indices"]))
              and a.result["answer"] == b.result["answer"]
              for a, b in zip(cold, warm)),
          "nvsa: cold and warm passes differ")
    check(np.array_equal(got_idx, want_idx),
          "nvsa: served indices differ from nvsa.solve's factorization")
    check(np.array_equal(got_ans, np.asarray(want["answer"])),
          f"nvsa: answers {got_ans} != nvsa.solve "
          f"{np.asarray(want['answer'])}")
    sims_diff = float(np.max(np.abs(got_sims - np.asarray(want["sims"]))))
    check(sims_diff <= NVSA_SIMS_ATOL,
          f"nvsa: candidate sims differ by {sims_diff} > {NVSA_SIMS_ATOL}")
    mean_iters = float(np.mean([np.mean(r.iterations) for r in warm]))
    iters_equal = all(np.array_equal(np.asarray(r.iterations),
                                     np.asarray(want["fact_iters"][b]))
                      for b, r in enumerate(warm))
    expected = kernel_expected(fz.fused_sweep_eligible(spec.cfg))
    kernel = has_kernel(eng._sweeps, eng.qs, eng.state, jnp.int32(1))
    check(kernel == expected,
          f"nvsa: sweep tpu_custom_call present={kernel}, expected {expected}")
    return {"cold_s": cold_s, "run_s": warm_s, "max_diff": sims_diff,
            "tol": NVSA_SIMS_ATOL, "kernel": kernel,
            "kernel_expected": expected,
            "detail": f"tasks={tasks} answers={got_ans.tolist()} "
                      f"iterations_equal={iters_equal} "
                      f"mean_iters={mean_iters:.2f}"}


def lvrf_phase(seed: int, rows: int = 64, slots: int = 32) -> dict:
    """Serve seeded clean LVRF rows through Runtime -> Engine with the fused
    Pallas sweep and, side by side, the unfused Jacobi engine."""
    cfg = lvrf.LVRFConfig()
    spec_f = engine.registry.build("lvrf_rows", jax.random.PRNGKey(seed),
                                   fused_step=True)
    spec_u = engine.registry.build("lvrf_rows", jax.random.PRNGKey(seed),
                                   synchronous=True)
    atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(seed))[0], cfg)
    vals = np.random.default_rng(seed).integers(0, cfg.n_values, (rows, 3))
    qs = lvrf.encode_row(atoms, jnp.asarray(vals), cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), rows)
    eng_f = engine.Engine(spec_f, slots=slots)
    eng_u = engine.Engine(spec_u, slots=slots)
    subs = [(name, qs[i], {"keys": keys[i][None]})
            for i in range(rows) for name in ("fused", "unfused")]
    with _runtime({"fused": eng_f, "unfused": eng_u}) as rt:
        cold, warm, cold_s, warm_s = timed_twice(rt, subs)

    def field(reqs, name, f):
        return np.stack([np.asarray(f(r)) for r in reqs[name::2]])

    fused_idx = field(warm, 0, lambda r: r.factorization.indices[0])
    check(np.array_equal(field(cold, 0, lambda r: r.factorization.indices[0]),
                         fused_idx), "lvrf: cold and warm passes differ")
    converged = field(warm, 0, lambda r: r.factorization.converged[0])
    check(np.array_equal(fused_idx[converged], vals[converged]),
          "lvrf: a converged row did not decode to its seeded values")
    check(converged.mean() >= LVRF_MIN_CONVERGED,
          f"lvrf: only {int(converged.sum())}/{rows} rows converged")
    check(np.array_equal(fused_idx,
                         field(warm, 1, lambda r: r.factorization.indices[0])),
          "lvrf: fused and unfused engines decode differently")
    check(np.array_equal(field(warm, 0, lambda r: r.iterations),
                         field(warm, 1, lambda r: r.iterations)),
          "lvrf: fused and unfused iteration counts differ")
    score_diff = float(np.max(np.abs(
        field(warm, 0, lambda r: r.factorization.scores)
        - field(warm, 1, lambda r: r.factorization.scores))))
    check(score_diff <= LVRF_SCORE_ATOL,
          f"lvrf: scores differ by {score_diff} > {LVRF_SCORE_ATOL}")
    expected = kernel_expected(True)
    kernel = has_kernel(eng_f._sweeps, eng_f.qs, eng_f.state, jnp.int32(1))
    check(kernel == expected,
          f"lvrf: fused sweep tpu_custom_call present={kernel}, "
          f"expected {expected}")
    return {"cold_s": cold_s, "run_s": warm_s, "max_diff": score_diff,
            "tol": LVRF_SCORE_ATOL, "kernel": kernel,
            "kernel_expected": expected,
            "detail": f"rows={rows} slots={slots} "
                      f"converged={int(converged.sum())}/{rows} "
                      f"sweeps fused={eng_f.sweeps_total} "
                      f"unfused={eng_u.sweeps_total}"}


def lm_phase(seed: int, cfg=None, prompts: int = 4, prompt_len: int = 64,
             new_tokens: int = 16) -> dict:
    """Greedy generation through Runtime -> LMEngine on the paged KV pool
    with the flash-decode kernel; first-token logits against the contiguous
    dense-attention layout on the same weights."""
    if cfg is None:
        cfg = dataclasses.replace(ARCHS["llama3.2-3b"].full(),
                                  param_dtype=jnp.bfloat16)
    params, _ = T.init(jax.random.PRNGKey(seed), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (prompts, prompt_len), 0, cfg.vocab)
    max_len = prompt_len + new_tokens + 1
    paged = PagedConfig()
    lm = LMEngine(cfg, params, slots=prompts, max_len=max_len,
                  prompt_len_hint=prompt_len, paged=paged)
    subs = [("lm", toks[i], {"max_new_tokens": new_tokens})
            for i in range(prompts)]
    with _runtime({"lm": lm}) as rt:
        cold, warm, cold_s, warm_s = timed_twice(rt, subs)
    tokens = [r.result["tokens"] for r in warm]
    check([r.result["tokens"] for r in cold] == tokens,
          "lm: cold and warm passes generated different tokens")
    check(all(len(t) == new_tokens for t in tokens),
          "lm: a request stopped short of its new-token budget")

    # The same first decode step on both KV layouts, same weights.
    host = np.asarray(toks, np.int32)
    last, active = host[:, -1:], np.ones(prompts, bool)
    lens = np.full(prompts, prompt_len - 1)
    pg = PagedKV(cfg, params, prompts, max_len, paged)
    ct = ContiguousKV(cfg, params, prompts, max_len)
    first = {}
    for name, kv in (("paged", pg), ("contiguous", ct)):
        for s in range(prompts):
            kv.prefill(s, host[s])
        first[name] = np.asarray(kv.decode(last, lens, active)[0][:, -1],
                                 np.float32)
    got, ref = first["paged"], first["contiguous"]
    check(bool(np.isfinite(got).all() and np.isfinite(ref).all()),
          "lm: non-finite logits")
    rel = float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-9))
    check(rel <= LM_LOGIT_RTOL,
          f"lm: paged vs contiguous first-token logits differ by {rel} "
          f"(relative) > {LM_LOGIT_RTOL}")
    check([int(t[0]) for t in tokens] == got.argmax(-1).tolist(),
          "lm: served first tokens are not the argmax of the paged logits")
    kernel = has_kernel(
        pg._decode, params, pg.pool, jnp.asarray(pg.blocks.table()),
        jnp.asarray(lens, jnp.int32), jnp.zeros((prompts, 1), jnp.int32),
        jnp.asarray(active))
    expected = kernel_expected(True)
    check(kernel == expected,
          f"lm: paged decode tpu_custom_call present={kernel}, "
          f"expected {expected}")
    argmax_agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    return {"cold_s": cold_s, "run_s": warm_s, "max_diff": rel,
            "tol": LM_LOGIT_RTOL, "kernel": kernel,
            "kernel_expected": expected,
            "detail": f"arch={cfg.name} layers={cfg.n_layers} "
                      f"d_model={cfg.d_model} params={T.param_count(params)} "
                      f"prompts={prompts}x{prompt_len} new={new_tokens} "
                      f"first_token_argmax_agree={argmax_agree} "
                      f"greedy_tokens={tokens}"}


def sharded_phase(seed: int, tasks: int = 4, rows: int = 64,
                  slots: int = 32) -> dict:
    """ShardedEngine on every device: 4x1 replicated codebooks (NVSA) and
    2x2 row-sharded codebooks with the fused LVRF sweep, each against a
    one-device Engine in this process."""
    n_dev = len(jax.devices())
    check(n_dev >= 4, f"sharded: needs 4 devices, found {n_dev}")
    mesh41, mesh22 = make_host_mesh(4, 1), make_host_mesh(2, 2)
    for mesh in (mesh41, mesh22):
        ids = {d.id for d in mesh.devices.flat}
        check(len(ids) == 4, f"sharded: mesh {dict(mesh.shape)} spans "
                             f"devices {sorted(ids)}, not four distinct ones")

    # NVSA: seeded attribute queries plus perception-like noise
    ncfg = nvsa.NVSAConfig()
    nspec = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(seed),
                                  cfg=ncfg)
    n = tasks * 8
    k_idx, k_noise, k_fact = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    attrs = jnp.stack([jax.random.randint(jax.random.fold_in(k_idx, a), (n,),
                                          0, m)
                       for a, m in enumerate(nvsa.ATTR_SIZES)], axis=-1)
    nq = nvsa.target_query(nspec.codebooks, attrs, ncfg)
    nq = nq + 0.5 * jnp.std(nq) * jax.random.normal(k_noise, nq.shape)
    nkeys = jax.random.split(k_fact, n).reshape(tasks, 8, -1)
    # LVRF: clean seeded rows through the fused sweep
    lcfg = lvrf.LVRFConfig()
    lspec = engine.registry.build("lvrf_rows", jax.random.PRNGKey(seed),
                                  fused_step=True)
    atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(seed))[0],
                            lcfg)
    vals = np.random.default_rng(seed).integers(0, lcfg.n_values, (rows, 3))
    lq = lvrf.encode_row(atoms, jnp.asarray(vals), lcfg)
    lkeys = jax.random.split(jax.random.PRNGKey(seed + 2), rows)

    engines = {
        "nvsa_4x1": engine.ShardedEngine(nspec, mesh=mesh41, slots=slots,
                                         codebook_placement="replicated"),
        "nvsa_1": engine.Engine(nspec, slots=slots),
        "lvrf_2x2": engine.ShardedEngine(lspec, mesh=mesh22, slots=slots,
                                         codebook_placement="rows"),
        "lvrf_1": engine.Engine(lspec, slots=slots),
    }
    subs = [(name, nq[b * 8:(b + 1) * 8], {"keys": nkeys[b]})
            for b in range(tasks) for name in ("nvsa_4x1", "nvsa_1")]
    subs += [(name, lq[i], {"keys": lkeys[i][None]})
             for i in range(rows) for name in ("lvrf_2x2", "lvrf_1")]
    with _runtime(engines) as rt:
        cold, warm, cold_s, warm_s = timed_twice(rt, subs)

    def pairs(reqs, lo, hi):
        sh, one = reqs[lo:hi:2], reqs[lo + 1:hi:2]
        return (np.stack([np.asarray(r.factorization.indices) for r in sh]),
                np.stack([np.asarray(r.factorization.indices) for r in one]),
                np.stack([np.asarray(r.factorization.scores) for r in sh]),
                np.stack([np.asarray(r.factorization.scores) for r in one]))

    nb = 2 * tasks
    ni_s, ni_1, ns_s, ns_1 = pairs(warm, 0, nb)
    li_s, li_1, ls_s, ls_1 = pairs(warm, nb, len(warm))
    check(np.array_equal(pairs(cold, 0, nb)[0], ni_s)
          and np.array_equal(pairs(cold, nb, len(cold))[0], li_s),
          "sharded: cold and warm passes differ")
    check(np.array_equal(ni_s, ni_1),
          "sharded: 4x1 replicated NVSA decodes differ from one-chip Engine")
    check(np.array_equal(li_s, li_1),
          "sharded: 2x2 rows LVRF decodes differ from one-chip Engine")
    l_conv = np.stack([np.asarray(r.factorization.converged[0])
                       for r in warm[nb::2]])
    check(np.array_equal(li_s[l_conv, 0], vals[l_conv])
          and l_conv.mean() >= LVRF_MIN_CONVERGED,
          "sharded: 2x2 rows LVRF did not decode the converged clean rows")
    for name in ("nvsa_4x1", "lvrf_2x2"):
        got = len(engines[name].state.iters.sharding.device_set)
        check(got == 4, f"sharded: {name} state lives on {got} devices")
    # rows placement may reassociate the projection psum (fp row sums), so
    # the score difference is reported, not required to be zero
    diff = float(max(np.max(np.abs(ns_s - ns_1)), np.max(np.abs(ls_s - ls_1))))
    sweep = engines["lvrf_2x2"]
    kernel = has_kernel(jax.jit(lambda qs, s, b: sweep._sweeps(qs, s, b)),
                        sweep.qs, sweep.state, jnp.int32(1))
    expected = kernel_expected(True)
    check(kernel == expected,
          f"sharded: 2x2 fused sweep tpu_custom_call present={kernel}, "
          f"expected {expected}")
    return {"cold_s": cold_s, "run_s": warm_s, "max_diff": diff,
            "tol": None, "kernel": kernel, "kernel_expected": expected,
            "detail": f"meshes 4x1+2x2 over devices "
                      f"{sorted(d.id for d in mesh41.devices.flat)} "
                      f"nvsa queries={n} lvrf rows={rows} "
                      f"sweeps nvsa 4x1={engines['nvsa_4x1'].sweeps_total} "
                      f"1={engines['nvsa_1'].sweeps_total} "
                      f"lvrf 2x2={sweep.sweeps_total} "
                      f"1={engines['lvrf_1'].sweeps_total}"}


# ---------------------------------------------------------------------------

def run_phase(name: str, clock: CompileClock, fn, *args) -> dict:
    c0, n0, t0 = clock.seconds, clock.count, time.perf_counter()
    rep = fn(*args)
    rep.update(compile_s=clock.seconds - c0, compiles=clock.count - n0,
               phase_s=time.perf_counter() - t0)
    print(f"[{name}] compile_s={rep['compile_s']:.3f} "
          f"(backend compiles: {rep['compiles']}) cold_s={rep['cold_s']:.3f} "
          f"run_s={rep['run_s']:.3f} phase_s={rep['phase_s']:.3f}")
    print(f"[{name}] compile_cache={jax.config.jax_compilation_cache_dir}")
    print(f"[{name}] max_diff={rep['max_diff']!r} tol={rep['tol']!r}")
    print(f"[{name}] tpu_custom_call={rep['kernel']} "
          f"(kernel on this path: {rep['kernel_expected']})")
    print(f"[{name}] {rep['detail']}", flush=True)
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip ShardedEngine phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the first device is {dev.platform!r}, not a TPU; "
              "refusing to run", file=sys.stderr)
        return 2
    cache_dir = programs.enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"compile cache: {cache_dir}")
    clock = CompileClock()
    if args.chips == 4:
        run_phase("sharded", clock, sharded_phase, args.seed)
    else:
        run_phase("nvsa", clock, nvsa_phase, args.seed)
        run_phase("lvrf", clock, lvrf_phase, args.seed)
        run_phase("lm", clock, lm_phase, args.seed)
    print(f"total: compile_s={clock.seconds:.3f} "
          f"backend_compiles={clock.count}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
