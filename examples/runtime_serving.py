"""Mixed neurosymbolic + LM traffic through ONE online serving runtime.

Three very differently shaped engines behind the same async ``Runtime``:
NVSA RPM abduction (unitary block-code factorization), LVRF row decoding
(bipolar MAP), and transformer greedy decode (``LMEngine`` over a KV
layout of ``repro.lm.kv``).  Requests are submitted from the caller
thread and complete on the background stepper, which picks the next engine
by adSCH-modeled step cost x queue depth; the LVRF engine additionally opts
into EWMA-driven slot re-tuning — watch its ``slots`` change mid-run with
zero effect on results (warm handoff).

    PYTHONPATH=src python examples/runtime_serving.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro import runtime as rt
from repro.configs.registry import ARCHS
from repro.models import lvrf, nvsa
from repro.nn import transformer as T

rng = np.random.default_rng(0)

# --- the three engines ----------------------------------------------------
ncfg = nvsa.NVSAConfig()
nspec = engine.registry.build("nvsa_abduction", jax.random.PRNGKey(0), cfg=ncfg)

lspec = engine.registry.build("lvrf_rows", jax.random.PRNGKey(0))
lcfg = lvrf.LVRFConfig()
atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(0))[0], lcfg)
# deliberately over-provisioned for an assumed 1000 rps of row traffic; the
# live EWMA estimate will say otherwise and the runtime will shrink it
lvrf_eng = engine.Engine(lspec, slots=16)

mcfg = ARCHS["llama3.2-3b"].smoke()
params, _ = T.init(jax.random.PRNGKey(0), mcfg)
lm_eng = rt.LMEngine(mcfg, params, slots=2, max_len=48)
print(f"[lm] decode_per_step={lm_eng.decode_per_step} (adSCH-derived from "
      f"the registered lm_decode StageGraph)")

runtime = rt.Runtime()
runtime.register("nvsa", engine.Engine(nspec, slots=8))
# re-tune on EWMA drift, pricing candidates by TIMING the compiled sweep
# (the analytic cell-pool model is device-seconds; the machine serving this
# example is a host CPU, so measured cost is the honest basis)
runtime.register("lvrf", lvrf_eng, retune=rt.RetunePolicy(
    threshold=2.0, check_every=1, baseline_rps=1000.0, candidates=(4, 8, 16),
    use_measured_cost=True))
runtime.register("lm", lm_eng)

# --- mixed traffic, async -------------------------------------------------
attrs = jnp.asarray(rng.integers(0, (5, 6, 10), (8, 3)))
cand = nvsa.target_query(nspec.codebooks,
                         jnp.asarray(rng.integers(0, (5, 6, 10), (8, 3))),
                         ncfg)
vals = jnp.asarray(rng.integers(0, lcfg.n_values, (12, 3)))
rows = lvrf.encode_row(atoms, vals, lcfg)  # encoded up front: submits burst
with runtime:
    g_nvsa = runtime.submit("nvsa", nvsa.target_query(nspec.codebooks, attrs,
                                                      ncfg),
                            meta={"cand": cand})
    g_lvrf = [runtime.submit("lvrf", rows[i]) for i in range(12)]
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (6,), 0, mcfg.vocab)
               for i in range(3)]
    g_lm = [runtime.submit("lm", p, max_new_tokens=8) for p in prompts]
    print(f"[submit] 1 NVSA task + 12 LVRF rows + 3 LM prompts in flight "
          f"(returns immediately; stepper thread serves)")

    req = runtime.result(g_nvsa, timeout=600)
    print(f"[nvsa] answer={req.result['answer']} "
          f"iters/query={req.iterations.tolist()} "
          f"latency={req.latency_s * 1e3:.0f}ms")
    decoded = [runtime.result(g, timeout=600).result["values"][0].tolist()
               for g in g_lvrf]
    print(f"[lvrf] decoded rows: {decoded[:4]}... "
          f"(truth {np.asarray(vals[:4]).tolist()}...)")
    for g in g_lm:
        r = runtime.result(g, timeout=600)
        print(f"[lm] request {r.id}: tokens={r.result['tokens']}")

    stats = runtime.stats()

print(f"[retune] lvrf slots now {lvrf_eng.slots} after "
      f"{stats['lvrf']['telemetry']['retunes']} EWMA-triggered re-tune(s) "
      f"(arrival estimate "
      f"{stats['lvrf']['telemetry']['arrival_rate_rps']:.1f} rps)")
for name in ("nvsa", "lvrf", "lm"):
    t = stats[name]["telemetry"]
    print(f"[stats] {name}: completed={t['completed']} "
          f"p50={t['latency_p50_ms'] and round(t['latency_p50_ms'])}ms "
          f"util={t['utilization']:.2f}")
