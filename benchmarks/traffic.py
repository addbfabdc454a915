"""Trace-driven load harness: seeded arrival traces over mixed traffic.

ROADMAP item 4's missing piece: replay bursty/diurnal/adversarial arrival
patterns over mixed nvsa+lvrf+lm traffic and report per-class SLO
attainment as the system's steady-state contract.  The harness runs the
same trace through TWO legs with different guarantees:

* **structural leg** (``replay_structural``) — a single-threaded
  discrete-event replay: arrivals land on a virtual clock, one
  deterministic SFQ rule (min virtual time, cost-weighted advance — the
  same math as ``Runtime._pick``) chooses which engine steps next.  No
  threads, no wall-clock in the loop, per-request pinned PRNG keys —
  so the submit sequence, the results (bit-equal), and the structural
  counters (sweeps, dispatches, KV bytes) are exactly reproducible.
  These counters are what ``check_regression.py`` gates.

* **runtime leg** (``replay_runtime``) — the real threaded
  :class:`repro.runtime.Runtime` under a live recorder: submissions
  sleep until each arrival's (scaled) trace time, classes and SLO
  targets flow through ``submit(class_=...)``, optionally one engine
  runs under a seeded :class:`ChaosEngine`.  This leg produces the
  per-class attainment snapshot, the span-derived attribution report,
  and the Chrome trace.  Its wall-clock numbers are REPORTED, never
  gated (CPU/interpret-mode timing is not predictive).

A third, fleet-controlled structural leg replays an ``overload`` trace
(sustained arrivals above capacity, mixed priority classes) under
:func:`overload_fleet` policy, so the controller's admission / preemption /
brownout / rebalance decision counters are deterministic and gated too.

``python -m benchmarks.traffic`` writes the unified BENCH envelope
(structural counters + SLO attainment + attribution summary) and the
Chrome trace; ``--events/--seed/--kind`` scale it for CI smoke runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import write_bench
from repro import engine as eng_mod
from repro import obs
from repro import runtime as rt
from repro.configs.registry import ARCHS
from repro.core import factorizer as fz
from repro.models import lvrf, nvsa
from repro.nn import transformer as T
from repro.runtime import faults as flt
from repro.runtime.protocol import step_cost_seconds

TRACE_KINDS = ("bursty", "diurnal", "adversarial", "overload")

#: Engine mix weights: nvsa factorizations and lvrf row decodes dominate,
#: LM generations are the heavy minority class (one costs many steps).
DEFAULT_MIX = (("nvsa", 3), ("lvrf", 4), ("lm", 1))

LM_GEN = 8  # tokens generated per LM request
_KIND_SALT = {k: i + 1 for i, k in enumerate(TRACE_KINDS)}

#: Priority-class mix for ``overload`` traces: a small latency-sensitive
#: minority swamped by best-effort bulk — the shape fleet admission
#: control exists for.
OVERLOAD_CLASSES = (("interactive", 1), ("best_effort", 3))

#: Engine mix for ``overload`` traces: weighted toward the multi-step LM
#: engine so live rows actually span control ticks — the precondition for
#: priority preemption (single-step symbolic requests never hold a slot
#: long enough to be worth preempting).
OVERLOAD_MIX = (("nvsa", 2), ("lvrf", 3), ("lm", 3))


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One trace event: at trace-time ``t`` submit payload ``idx`` of
    ``engine``'s pool.  ``cls`` is the priority class (empty for the
    classless trace kinds — replays then fall back to the engine name)."""

    t: float
    engine: str
    idx: int
    cls: str = ""


# -- trace generation ------------------------------------------------------


def make_trace(kind: str, *, seed: int = 0, events: int = 48,
               duration_s: float = 1.0, mix=None) -> list[Arrival]:
    """Seeded arrival trace of `events` arrivals over ``[0, duration_s)``.

    * ``bursty`` — Poisson-ish bursts separated by idle gaps (the paper's
      irregular-workload argument at the traffic level);
    * ``diurnal`` — sinusoidally modulated rate (a day compressed into the
      trace window), sampled by thinning;
    * ``adversarial`` — a steady trickle plus one synchronized spike of
      the heaviest engine's requests at mid-trace (worst case for a
      virtual-time scheduler: one class tries to monopolize the stepper);
    * ``overload`` — sustained arrivals at a rate the fleet cannot keep up
      with, tagged with mixed priority classes (``OVERLOAD_CLASSES``): the
      input the fleet controller's admission/preemption/brownout policies
      are exercised (and gated) against.
    """
    if kind not in TRACE_KINDS:
        raise ValueError(f"unknown trace kind {kind!r}; one of {TRACE_KINDS}")
    if mix is None:
        mix = OVERLOAD_MIX if kind == "overload" else DEFAULT_MIX
    rng = np.random.default_rng([seed, _KIND_SALT[kind]])
    names = [n for n, _ in mix]
    w = np.asarray([float(x) for _, x in mix])
    w = w / w.sum()

    if kind == "bursty":
        times = []
        t = 0.0
        while len(times) < events:
            burst = int(rng.integers(3, 9))
            for _ in range(burst):
                if len(times) >= events:
                    break
                t += float(rng.exponential(duration_s / (events * 6)))
                times.append(t)
            t += float(rng.exponential(duration_s / 6))  # off period
        times = np.asarray(times)
        times = times / times.max() * duration_s * 0.95
    elif kind == "diurnal":
        # thinning against rate(t) = 1 + 0.9 sin(2 pi t / duration)
        times = []
        while len(times) < events:
            cand = float(rng.uniform(0, duration_s))
            rate = 1.0 + 0.9 * np.sin(2 * np.pi * cand / duration_s)
            if rng.uniform(0, 1.9) < rate:
                times.append(cand)
        times = np.sort(np.asarray(times))
    elif kind == "adversarial":
        n_spike = events // 2
        trickle = np.sort(rng.uniform(0, duration_s, events - n_spike))
        spike = np.full(n_spike, duration_s * 0.5)
        times = np.sort(np.concatenate([trickle, spike]))
    else:  # overload: sustained pressure, no idle gaps to drain into
        gaps = rng.exponential(duration_s / events, size=events)
        times = np.cumsum(gaps)
        times = times / times.max() * duration_s * 0.95

    engines = [names[i] for i in rng.choice(len(names), size=events, p=w)]
    if kind == "adversarial":
        # the spike is all one (heaviest) class: everything landing at the
        # spike instant targets the LAST engine in the mix (lm by default)
        heavy = names[-1]
        engines = [heavy if abs(t - duration_s * 0.5) < 1e-12 else e
                   for t, e in zip(times, engines)]
    classes = [""] * events
    if kind == "overload":
        # class draw happens AFTER the engine draw and only on this branch,
        # so the older kinds' rng streams (and digests) are untouched
        cnames = [c for c, _ in OVERLOAD_CLASSES]
        cw = np.asarray([float(x) for _, x in OVERLOAD_CLASSES])
        classes = [cnames[j] for j in
                   rng.choice(len(cnames), size=events, p=cw / cw.sum())]
    counts: dict[str, int] = {n: 0 for n in names}
    out = []
    for t, e, c in zip(times, engines, classes):
        out.append(Arrival(float(t), e, counts[e], c))
        counts[e] += 1
    return out


# -- shared problem pools / engines ----------------------------------------


def build_problems(seed: int = 0, *, n_nvsa: int = 24, n_lvrf: int = 32,
                   n_lm: int = 12):
    """Deterministic payload pools; trace ``idx`` indexes them modulo size.
    Per-request pinned PRNG keys make replays bit-equal regardless of
    fill/burst interleave."""
    ncfg = nvsa.NVSAConfig()
    cbs, mask = nvsa.make_codebooks(jax.random.PRNGKey(0), ncfg)
    k_idx, k_noise, k_fact = jax.random.split(jax.random.PRNGKey(seed), 3)
    idxs = jnp.stack([jax.random.randint(jax.random.fold_in(k_idx, a),
                                         (n_nvsa,), 0, n)
                      for a, n in enumerate(nvsa.ATTR_SIZES)], axis=-1)
    nq = fz.bind_combo(cbs, idxs, ncfg.factorizer.vsa)
    nq = nq + 1.4 * jnp.std(nq) * jax.random.normal(k_noise, nq.shape)
    nkeys = jax.random.split(k_fact, n_nvsa)
    nspec = eng_mod.ServeSpec("bench_nvsa_queries", cbs, ncfg.factorizer,
                              mask)

    lspec = eng_mod.registry.build("lvrf_rows", jax.random.PRNGKey(0))
    lcfg = lvrf.LVRFConfig()
    atoms = lvrf.init_atoms(jax.random.split(jax.random.PRNGKey(0))[0], lcfg)
    vals = jnp.asarray(np.random.default_rng(seed).integers(
        0, lcfg.n_values, (n_lvrf, 3)))
    lq = lvrf.encode_row(atoms, vals, lcfg)
    lkeys = jax.random.split(jax.random.PRNGKey(seed + 1), n_lvrf)

    mcfg = ARCHS["llama3.2-3b"].smoke()
    params, _ = T.init(jax.random.PRNGKey(0), mcfg)
    prompts = [jax.random.randint(jax.random.PRNGKey(100 + i), (6,), 0,
                                  mcfg.vocab) for i in range(n_lm)]
    return {"nvsa": (nspec, nq, nkeys), "lvrf": (lspec, lq, lkeys),
            "lm": (mcfg, params, prompts)}


def build_engines(problems, engines=("nvsa", "lvrf", "lm")) -> dict:
    out: dict = {}
    if "nvsa" in engines:
        out["nvsa"] = eng_mod.Engine(problems["nvsa"][0], slots=4,
                                     sweeps_per_step=4)
    if "lvrf" in engines:
        out["lvrf"] = eng_mod.Engine(problems["lvrf"][0], slots=4)
    if "lm" in engines:
        mcfg, params, _ = problems["lm"]
        out["lm"] = rt.LMEngine(mcfg, params, slots=2,
                                max_len=6 + LM_GEN + 1, decode_per_step=2)
    return out


def _warm(engines, problems) -> None:
    """Compile each engine's programs outside the measured region, then
    reset the serving counters so structural baselines exclude warmup."""
    if "nvsa" in engines:
        _, nq, nkeys = problems["nvsa"]
        engines["nvsa"].submit(nq[0], keys=nkeys[:1])
    if "lvrf" in engines:
        _, lq, lkeys = problems["lvrf"]
        engines["lvrf"].submit(lq[0], keys=lkeys[:1])
    if "lm" in engines:
        _, _, prompts = problems["lm"]
        engines["lm"].submit(prompts[0], max_new_tokens=2)
    for name, e in engines.items():
        e.drain()
        e.completed.clear()
        if name == "lm":
            e.steps_total = e.tokens_total = 0
            e.prefill_dispatches = e.decode_dispatches = 0
            e.kv_bytes_touched = 0
        else:
            e.sweeps_total = e.steps_total = 0


def _submit(engines, problems, ev: Arrival):
    if ev.engine == "nvsa":
        _, nq, nkeys = problems["nvsa"]
        i = ev.idx % nq.shape[0]
        return nq[i], {"keys": nkeys[i:i + 1]}
    if ev.engine == "lvrf":
        _, lq, lkeys = problems["lvrf"]
        i = ev.idx % lq.shape[0]
        return lq[i], {"keys": lkeys[i:i + 1]}
    _, _, prompts = problems["lm"]
    return prompts[ev.idx % len(prompts)], {"max_new_tokens": LM_GEN}


def _result_digest(results: list) -> str:
    """Stable content hash over the ordered result payloads — the
    determinism probe (same seed -> bit-equal results)."""
    h = hashlib.sha256()
    for engine, idx, res in results:
        h.update(f"{engine}:{idx}".encode())
        # results are pytrees (dicts, namedtuples, token lists): hash the
        # ordered leaves so any payload shape digests the same way
        for leaf in jax.tree_util.tree_leaves(res):
            h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


# -- leg 1: deterministic structural replay --------------------------------


def replay_structural(trace, problems, *, steps_per_s: float | None = None,
                      engines=None, fleet=None) -> dict:
    """Single-threaded discrete-event replay of `trace`.

    Virtual time advances by ``1 / steps_per_s`` per engine step (service
    capacity) and jumps to the next arrival when every engine is idle;
    engine choice is the Runtime's SFQ rule (min virtual time, virtual
    time advanced by modeled step cost / backlog, start-time clamped).
    Everything is deterministic: no threads, no wall clock, pinned keys.

    ``fleet`` (a :class:`repro.runtime.FleetPolicy` or bound controller)
    puts the same :class:`~repro.runtime.FleetController` the threaded
    Runtime uses in the loop: every arrival goes through ``admit`` (shed
    arrivals never reach an engine; degraded ones get trimmed budgets;
    admitted ones carry their class priority), and ``control`` runs on the
    virtual clock after every step — so admission/preemption/brownout/
    rebalance decision counters are exactly reproducible and regression-
    gateable alongside the engine counters.
    """
    kinds = engines if engines is not None else \
        tuple(dict.fromkeys(ev.engine for ev in trace))
    engs = build_engines(problems, kinds)
    _warm(engs, problems)
    if steps_per_s is None:
        dur = max((ev.t for ev in trace), default=0.0) or 1.0
        steps_per_s = 3.0 * len(trace) / dur
    ctrl = None
    cls_of: dict[tuple[str, int], str] = {}
    if fleet is not None:
        ctrl = fleet if isinstance(fleet, rt.FleetController) \
            else rt.FleetController(fleet)
        units = {n: int(getattr(e, "sweeps_per_step", 0)
                        or getattr(e, "decode_per_step", 0) or 1)
                 for n, e in engs.items()}
        # one engine step == 1/steps_per_s virtual seconds, so the modeled
        # per-unit cost is that, split across the step's units
        ctrl.bind(engs,
                  unit_s_fn=lambda n: (1.0 / steps_per_s) / units[n],
                  class_of=lambda n, rid: cls_of.get((n, rid)))
    vt = {n: 0.0 for n in engs}
    vclock = 0.0
    was_busy: set = set()
    now = 0.0
    i = 0
    submit_seq: list[tuple[str, int]] = []
    shed_seq: list[tuple[str, int]] = []
    submitted: dict[str, dict] = {n: {} for n in engs}  # local id -> idx
    results: list = []
    steps = 0
    while i < len(trace) or any(e.in_flight for e in engs.values()):
        while i < len(trace) and trace[i].t <= now:
            ev = trace[i]
            i += 1
            payload, kw = _submit(engs, problems, ev)
            if ctrl is not None:
                cls = ev.cls or ev.engine
                decision = ctrl.admit(ev.engine, cls, now=now)
                if decision.action == "shed":
                    shed_seq.append((ev.engine, ev.idx))
                    continue
                kw = decision.apply(kw)
                kw["priority"] = decision.priority
            rid = engs[ev.engine].submit(payload, **kw)
            if ctrl is not None:
                cls_of[(ev.engine, rid)] = cls
            submitted[ev.engine][rid] = ev.idx
            submit_seq.append((ev.engine, ev.idx))
        busy = [n for n, e in engs.items() if e.in_flight]
        if not busy:
            if i < len(trace):
                now = trace[i].t  # idle fleet: jump to the next arrival
                was_busy.clear()
                continue
            break
        # SFQ pick — the same math as Runtime._pick, minus the threads
        for n in busy:
            if n not in was_busy:
                vt[n] = max(vt[n], vclock)
        was_busy = set(busy)
        pick = min(busy, key=lambda n: vt[n])
        vclock = vt[pick]
        finished = engs[pick].step()
        steps += 1
        backlog = engs[pick].in_flight + len(finished)
        vt[pick] += step_cost_seconds(engs[pick]) / max(1, backlog)
        now += 1.0 / steps_per_s
        if ctrl is not None:
            ctrl.control(now=now)
        for req in finished:
            idx = submitted[pick].pop(req.id)
            res = req.result if not hasattr(req, "tokens") else req.tokens
            results.append((pick, idx, res))
    counters = structural_counters(engs)
    out = {"submit_seq": submit_seq, "results": results,
           "digest": _result_digest(results), "steps": steps,
           "steps_per_s": steps_per_s, "structural": counters}
    if ctrl is not None:
        counters.update(ctrl.structural_counters())
        out["shed_seq"] = shed_seq
        out["fleet"] = ctrl.snapshot()
    return out


def structural_counters(engines: dict) -> dict:
    """The gated (deterministic, transferable) counters per engine."""
    out = {}
    for name, e in engines.items():
        if e.engine_kind == "lm":
            out[name] = {
                "steps": e.steps_total,
                "tokens_total": e.tokens_total,
                "prefill_dispatches": e.prefill_dispatches,
                "decode_dispatches": e.decode_dispatches,
                "kv_bytes_touched": e.kv_bytes_touched,
                "units_per_step": e.decode_per_step,
            }
        else:
            out[name] = {
                "steps": e.steps_total,
                "sweeps_total": e.sweeps_total,
                "units_per_step": e.sweeps_per_step,
                "psums_per_sweep": e._psums_per_sweep(),
                "pallas_calls_per_sweep":
                    1 if (e.spec.cfg is not None
                          and fz.fused_sweep_eligible(e.spec.cfg)) else 0,
            }
    return out


def overload_fleet(steps_per_s: float) -> rt.FleetPolicy:
    """The fleet policy the overload leg (and the CI overload scenario)
    runs under.  Thresholds are denominated in virtual step times
    (``1 / steps_per_s``) so the same policy works at any replay speed:
    best-effort work degrades past ~2 queued steps of estimated wait, is
    shed past ~4, and a sustained ~2.5-step backlog browns the fleet out;
    interactive work is never shed and never trimmed, and preempts
    best-effort rows out of live slots."""
    step_v = 1.0 / steps_per_s
    return rt.FleetPolicy(
        classes=(
            rt.PriorityClass("interactive", priority=0),
            rt.PriorityClass("best_effort", priority=3,
                             admit_wait_s=4 * step_v,
                             degrade_wait_s=2 * step_v,
                             preemptible=True, degradable=True),
        ),
        default_class="best_effort",
        max_preempt_per_tick=2,
        rebalance_every=8, rebalance_step=1, rebalance_ratio=1.5,
        min_slots=2,
        brownout=rt.BrownoutPolicy(enter_wait_s=2.5 * step_v,
                                   exit_wait_s=1 * step_v,
                                   enter_ticks=2, exit_ticks=2,
                                   lm_token_cap=4),
    )


def structural_suite(cfg: dict) -> dict:
    """Every deterministic counter the regression gate inspects, from one
    recorded config: the base trace replay plus — when the config carries
    an ``overload`` sub-dict — the fleet-controlled overload leg, whose
    per-engine counters and per-class fleet decision counters are merged
    in under ``overload_*`` keys.  Shared by ``bench()`` and
    ``check_regression._fresh_structural`` so the gate re-runs exactly
    what the baseline recorded."""
    problems = build_problems(cfg["seed"])
    trace = make_trace(cfg["kind"], seed=cfg["seed"], events=cfg["events"],
                       duration_s=cfg["duration_s"])
    base = replay_structural(trace, problems)
    out = {"structural": dict(base["structural"]), "steps": base["steps"],
           "steps_per_s": base["steps_per_s"], "digest": base["digest"]}
    ov = cfg.get("overload")
    if ov:
        otrace = make_trace("overload", seed=ov["seed"],
                            events=ov["events"],
                            duration_s=ov["duration_s"])
        sps = float(ov["steps_per_s"])
        res = replay_structural(otrace, problems, steps_per_s=sps,
                                fleet=overload_fleet(sps))
        for name, ctrs in res["structural"].items():
            out["structural"][f"overload_{name}"] = ctrs
        out["overload_digest"] = res["digest"]
        out["overload_fleet"] = res["fleet"]
    return out


# -- leg 2: runtime replay (SLO + attribution + chrome trace) --------------

DEFAULT_SLO = {
    "nvsa": obs.SLOTarget(20.0, percentile=95),
    "lvrf": obs.SLOTarget(20.0, percentile=95),
    "lm": obs.SLOTarget(60.0, percentile=95),
}


def replay_runtime(trace, problems, *, time_scale: float = 1.0,
                   slo=None, chaos_seed: int | None = None,
                   recorder=None) -> dict:
    """Replay `trace` through the real threaded Runtime under a recorder.

    Arrival times are honored (scaled by ``time_scale``) with wall-clock
    sleeps; each request is submitted with ``class_=`` its engine's pool
    name so the SLO tracker and the attribution report see per-class
    traffic.  ``chaos_seed`` wraps the lvrf engine in a seeded
    :class:`ChaosEngine` (one injected fault) so the report has a
    quarantine/replay episode to attribute.
    """
    kinds = tuple(dict.fromkeys(ev.engine for ev in trace))
    engs = build_engines(problems, kinds)
    _warm(engs, problems)
    rec = recorder if recorder is not None else obs.Recorder()
    if chaos_seed is not None and "lvrf" in engs:
        engs["lvrf"] = flt.ChaosEngine(engs["lvrf"], flt.FaultPlan(
            seed=chaos_seed, step_error_rate=0.4, max_faults=1))
    runtime = rt.Runtime(obs=rec, slo=dict(slo if slo is not None
                                           else DEFAULT_SLO),
                         failure=rt.FailurePolicy(
                             max_restarts=8, backoff_initial_s=0.01,
                             backoff_max_s=0.05))
    for name, e in engs.items():
        runtime.register(name, e)
    t_wall0 = time.perf_counter()
    with runtime:
        start = time.perf_counter()
        gids = []
        for ev in trace:
            lag = ev.t * time_scale - (time.perf_counter() - start)
            if lag > 0:
                time.sleep(lag)
            payload, kw = _submit(engs, problems, ev)
            gids.append(runtime.submit(ev.engine, payload,
                                       class_=ev.engine, **kw))
        runtime.drain(timeout=600, return_exceptions=True)
        slo_snap = runtime.stats()["slo"]
    wall_s = time.perf_counter() - t_wall0
    report = obs.attribution(rec)
    return {"slo": slo_snap, "report": report, "recorder": rec,
            "wall_s": wall_s, "gids": gids}


# -- CLI -------------------------------------------------------------------


def _slo_summary(slo: dict) -> dict:
    keep = ("submitted", "completed", "deadline_missed", "shed", "failed",
            "latency_p50_s", "latency_p95_s", "latency_p99_s", "target_s",
            "attainment", "attained", "deadline_miss_rate", "shed_rate")
    return {c: {k: row.get(k) for k in keep} for c, row in slo.items()}


def _attribution_summary(report: dict) -> dict:
    return {
        "coverage": report["coverage"],
        "engines": {e: {"steps": st["steps"],
                        "phase_s": {k: round(v, 6)
                                    for k, v in st["phase_s"].items()},
                        "span_drift_ratio": st["span_drift_ratio"]}
                    for e, st in report["engines"].items()},
        "classes": report["classes"],
    }


def overload_config(seed: int, events: int, duration_s: float) -> dict:
    """The overload leg's recorded replay config.  ``steps_per_s`` is
    pinned at one virtual step per FOUR mean inter-arrival gaps — far
    below what the batched multi-step requests need, i.e. sustained
    overload — and written into the envelope so the gate replays at the
    same speed."""
    return {"seed": seed, "events": events, "duration_s": duration_s,
            "steps_per_s": round(events / duration_s / 4.0, 6)}


def bench(kind: str = "bursty", *, seed: int = 0, events: int = 48,
          duration_s: float = 1.0, time_scale: float = 1.0,
          chaos_seed: int | None = 1, trace_out: str | None = None) -> dict:
    trace = make_trace(kind, seed=seed, events=events, duration_s=duration_s)
    problems = build_problems(seed)
    suite = structural_suite({
        "kind": kind, "seed": seed, "events": events,
        "duration_s": duration_s,
        "overload": overload_config(seed, events, duration_s)})
    live = replay_runtime(trace, problems, time_scale=time_scale,
                          chaos_seed=chaos_seed)
    if trace_out:
        live["recorder"].write_chrome_trace(trace_out)
    per_engine: dict[str, int] = {}
    for ev in trace:
        per_engine[ev.engine] = per_engine.get(ev.engine, 0) + 1
    return {
        "trace": {"kind": kind, "seed": seed, "events": events,
                  "duration_s": duration_s, "per_engine": per_engine},
        "structural": suite["structural"],
        "structural_steps": suite["steps"],
        "steps_per_s": suite["steps_per_s"],
        "digest": suite["digest"],
        "overload": {"digest": suite["overload_digest"],
                     "fleet": suite["overload_fleet"]},
        "slo": _slo_summary(live["slo"]),
        "attribution": _attribution_summary(live["report"]),
        "runtime_wall_s": round(live["wall_s"], 3),
        "chaos": {"seed": chaos_seed,
                  "enabled": chaos_seed is not None},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kind", default="bursty", choices=TRACE_KINDS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--events", type=int, default=48)
    ap.add_argument("--duration-s", type=float, default=1.0)
    ap.add_argument("--time-scale", type=float, default=1.0)
    ap.add_argument("--no-chaos", action="store_true")
    ap.add_argument("--out", default="BENCH_traffic.json")
    ap.add_argument("--trace-out", default=None,
                    help="also write the Chrome trace JSON here")
    args = ap.parse_args(argv)
    result = bench(args.kind, seed=args.seed, events=args.events,
                   duration_s=args.duration_s, time_scale=args.time_scale,
                   chaos_seed=None if args.no_chaos else 1,
                   trace_out=args.trace_out)
    env = write_bench(
        args.out, "traffic", result,
        workload=(f"{args.events} mixed nvsa+lvrf+lm arrivals, "
                  f"{args.kind} trace (seed {args.seed}) — deterministic "
                  "structural replay + live Runtime SLO replay"),
        timing_mode=("CPU wall clock for the runtime leg — NOT "
                     "TPU-predictive; the structural counters from the "
                     "deterministic leg are the gated signal"),
        config={"kind": args.kind, "seed": args.seed, "events": args.events,
                "duration_s": args.duration_s,
                "overload": overload_config(args.seed, args.events,
                                            args.duration_s),
                "chaos": not args.no_chaos})
    print(json.dumps({"slo": env["result"]["slo"],
                      "coverage": env["result"]["attribution"]["coverage"],
                      "digest": env["result"]["digest"],
                      "overload_fleet": env["result"]["overload"]["fleet"]},
                     indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
