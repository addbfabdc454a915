"""Request-level serving engine: continuous batching of reasoning queries.

The symbolic analogue of LM decode slotting (runtime/lm.py): the factorizer
state is a fixed-shape ``[N, F, D]`` batch riding ONE while_loop program, and
incoming factorization requests are slotted into rows as converged rows
retire — so the batch never drains to the slowest query the way a
batch-and-wait ``factorize_batch`` wave does.  Rows are fully independent in
the resonator sweep (every op is row-elementwise or a row-batched matmul), so
a request's trajectory — including its stochasticity stream — is bit-equal to
a solo :func:`repro.core.factorizer.factorize` call with the same key,
whichever slot and whichever sweep it lands on.

How many sweeps run between host-side retirement scans is an adSCH decision,
not a constant: :func:`derive_sweeps_per_step` prices one sweep of the full
slot batch and the declared neural stage with the paper's analytic cell-pool
model and picks the sweep burst that fits the neural overlap window
(Sec. VI-B's interleave granularity).
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.cogsim import model as hw_model
from repro.core import factorizer as fz
from repro.core import scheduler as sch
from repro.core.factorizer import sweep_cost_ops  # re-export (public API)
from repro.engine.registry import ServeSpec
from repro.engine.stage import stage_ops


def step_unit_ops(spec: ServeSpec, slots: int, *, data_shards: int = 1,
                  model_shards: int = 1) -> list:
    """Cost ops of ONE step unit of `spec` at this slot count.

    The seam that makes the adSCH step pricing workload-generic: a spec may
    declare its own ``step_ops`` (LM decode prices one token over the slot
    batch); factorizer specs default to one resonator sweep.
    """
    if spec.step_ops is not None:
        return spec.step_ops(slots, data_shards=data_shards,
                             model_shards=model_shards)
    if spec.cfg is None:
        raise ValueError(f"spec {spec.name!r} has neither step_ops nor a "
                         "FactorizerConfig to price a step from")
    return sweep_cost_ops(spec.cfg, slots, data_shards=data_shards,
                          model_shards=model_shards)


def derive_sweeps_per_step(spec: ServeSpec, slots: int, hw=hw_model.COGSYS, *,
                           data_shards: int = 1, model_shards: int = 1) -> int:
    """Sweep burst between retirement scans, from adSCH runtime estimates.

    With a declared graph the burst is the number of symbolic sweeps that fit
    the neural stages' makespan (the interleave window the hardware scheduler
    fills, Fig. 13b).  Without one, a fixed burst of 8 amortizes the
    host-side slotting scan.  With shards both sides are priced per device —
    the sweep including its cross-shard psums (collective ops on the ICI),
    the neural window scaled to its data-parallel slice — so a sharded
    engine's burst reflects that communication makes each sweep *longer*
    while row-sharding makes it *cheaper*.

    The "sweep" is whatever the spec declares as one step unit: specs with
    ``step_ops`` (e.g. ``lm_decode``, where a step is one decode token over
    the slot batch and the neural window is the prefill stage) are priced by
    those hints, factorizer specs by :func:`sweep_cost_ops`.
    """
    t_sweep = sch.schedule(
        step_unit_ops(spec, slots, data_shards=data_shards,
                      model_shards=model_shards), hw).makespan
    if spec.graph is not None and t_sweep > 0:
        neural = [st for st in spec.graph.stages if not st.symbolic]
        n_ops = stage_ops(neural, 0) if neural else []
        if n_ops and data_shards > 1:
            from repro.engine.sharding.costs import shard_ops

            n_ops = shard_ops(n_ops, data_shards)
        if n_ops:
            t_neural = sch.schedule(n_ops, hw).makespan
            return int(np.clip(round(t_neural / t_sweep), 1, 64))
    return 8


# Rolling latency windows are capped so non-destructive snapshot() readers
# (metrics scrapes, dashboards) can coexist with a serving loop that never
# calls the draining stats() — memory stays bounded either way.
LAT_WINDOW_CAP = 1024


def rolling_latency_ms(lats) -> dict:
    """p50/p99 (in ms) of one drained latency window, ``None`` when empty.

    The ONE percentile definition every serving stats surface uses
    (``Engine.stats``, ``runtime.LMEngine.stats``, runtime telemetry
    snapshots) — side-by-side reports must not disagree on interpolation.
    """
    if not lats:
        return {"latency_p50_ms": None, "latency_p99_ms": None}
    arr = np.asarray(lats)
    return {"latency_p50_ms": float(np.percentile(arr, 50) * 1e3),
            "latency_p99_ms": float(np.percentile(arr, 99) * 1e3)}


@dataclasses.dataclass
class Request:
    """One submitted reasoning request (1..k queries slotted independently)."""

    id: int
    queries: np.ndarray  # [k, D] float32, on the host until a slot takes it
    keys: np.ndarray  # [k, ...] one PRNG key per query, on the host
    meta: Any
    submit_time: float
    submit_sweep: int
    priority: int = 0  # queue order: lower serves first (fleet classes)
    iter_budget: int | None = None  # per-request cap on cfg.max_iters (brownout)
    rows: list = dataclasses.field(default_factory=list)  # per-query results
    result: Any = None  # postprocess output (or stacked FactorizerResult)
    factorization: Any = None  # stacked FactorizerResult over the k queries
    iterations: Any = None  # [k] int — matches a solo factorize() per query
    done_time: float | None = None
    done_sweep: int | None = None

    @property
    def num_queries(self) -> int:
        return self.queries.shape[0]

    @property
    def latency_s(self) -> float | None:
        return None if self.done_time is None else \
            self.done_time - self.submit_time


class Engine:
    """``submit()/step()/drain()`` continuous batching over one ServeSpec.

    One Engine serves one registered pipeline (fixed codebook shapes keep the
    sweep program static); NVSA abduction and LVRF row decoding run through
    this same class — see :mod:`repro.engine.pipelines`.
    """

    engine_kind = "factorizer"  # unified stats schema discriminator

    def __init__(self, spec: ServeSpec, *, slots: int = 32,
                 sweeps_per_step: int | None = None, hw=hw_model.COGSYS,
                 key: jax.Array | None = None, fused=None, obs=None,
                 clock=None):
        self.spec = spec
        self.slots = slots
        self.hw = hw
        # Observability seam: spans + metrics recorded AROUND the device
        # dispatches (never inside jitted code).  NULL default is a
        # behavioral no-op; Runtime.register rebinds obs/track/clock onto
        # engines built with the defaults so one recorder (and ONE monotonic
        # clock) covers the whole stack.
        self.obs = obs if obs is not None else obs_mod.NULL
        self.obs_track = spec.name
        self._default_clock = clock is None
        self._clock = clock if clock is not None else self.obs.clock
        # Kernel knobs for fused-eligible specs (cfg.fused_step &c. — see
        # factorizer.fused_sweep_eligible): a
        # repro.kernels.resonator_step.ops.FusedConfig or None (defaults).
        # Threaded into every make_resonator build, including post-resize
        # rebuilds and ShardedEngine's shard_map bodies.
        from repro.kernels.resonator_step.ops import FusedConfig
        if fused is not None and not isinstance(fused, FusedConfig):
            raise TypeError(
                f"Engine(fused=) expects a FusedConfig or None, got "
                f"{fused!r}; the fused sweep is requested via "
                "fused_step=True on the spec's FactorizerConfig")
        self.fused = fused
        self._sweeps_pinned = sweeps_per_step is not None
        self.sweeps_per_step = (self._derive_sweeps_per_step()
                                if sweeps_per_step is None else sweeps_per_step)
        self._key = key if key is not None else jax.random.PRNGKey(0)
        # sets self.qs / self.state / self._sweeps / self._refill_many /
        # self._decode — the seam a mesh-parallel engine overrides
        # (repro.engine.sharding.ShardedEngine lowers the same closures
        # through shard_map instead)
        self._build_programs()
        self._owner: list = [None] * slots  # (request, query_index) | None
        self._queue: deque = deque()
        self._next_id = 0
        self.completed: dict = {}
        self.sweeps_total = 0
        self.steps_total = 0
        self.resizes_total = 0
        self.recoveries_total = 0
        # All-time accounting kept incrementally: `completed` is a lookup the
        # runtime may evict resolved requests from, so totals must not scan it.
        self.completed_total = 0
        self._lat_sum = 0.0
        self._lat_window: list = []  # latencies since the last stats() snapshot
        self._step_cost_cache: float | None = None

    def _derive_sweeps_per_step(self) -> int:
        return derive_sweeps_per_step(self.spec, self.slots, self.hw)

    def _build_programs(self) -> None:
        """Compile the three device programs (sweep burst / refill / decode)
        and allocate the parked slot state."""
        spec, slots = self.spec, self.slots
        rs = fz.make_resonator(spec.codebooks, spec.cfg, spec.valid_mask,
                               fused=self.fused)
        self._rs = rs
        self.qs = jnp.zeros((slots, spec.dim), jnp.float32)
        st = rs.init(self.qs, jax.random.split(jax.random.PRNGKey(0), slots))
        self.state = st._replace(done=jnp.ones(slots, bool))  # all rows parked

        def run_sweeps(qs, s, budget):
            def cond(c):
                s, n = c
                return jnp.logical_and(n < budget, jnp.any(rs.active(s)))

            def body(c):
                s, n = c
                return rs.sweep(qs, s), n + 1

            return jax.lax.while_loop(cond, body, (s, jnp.int32(0)))

        self._sweeps = jax.jit(run_sweeps)
        self._refill_many = jax.jit(rs.refill_many)
        self._decode = jax.jit(rs.decode)
        self._record_structure()

    def _psums_per_sweep(self) -> int:
        """Cross-device psums ONE sweep dispatches (0 on a single device;
        the mesh engine overrides with its collectives contract)."""
        return 0

    def _record_structure(self) -> None:
        """Structural gauges — the transferable (non-wall-clock) signal —
        refreshed on every program (re)build: slot shape, burst size, and
        the per-sweep kernel/collective structure."""
        if not self.obs.enabled:
            return
        track = self.obs_track
        self.obs.gauge("slots", self.slots, engine=track)
        self.obs.gauge("units_per_step", self.sweeps_per_step, engine=track)
        self.obs.gauge("psums_per_sweep", self._psums_per_sweep(),
                       engine=track)
        self.obs.gauge(
            "pallas_calls_per_sweep",
            1 if (self.spec.cfg is not None
                  and fz.fused_sweep_eligible(self.spec.cfg)) else 0,
            engine=track)

    def bind_obs(self, obs, track: str | None = None) -> None:
        """Adopt a recorder after construction — the ``Runtime.register``
        seam: an engine built with the defaults joins the runtime's recorder
        (and its monotonic clock, keeping every layer's timestamps on one
        axis); an engine built with an explicit ``clock=`` keeps it."""
        self.obs = obs
        if track is not None:
            self.obs_track = track
        if self._default_clock:
            self._clock = obs.clock
        self._record_structure()

    # -- request intake ----------------------------------------------------

    def submit(self, queries, *, key=None, keys=None, meta=None,
               priority: int = 0, max_iters: int | None = None) -> int:
        """Enqueue a request of one or more query vectors; returns its id.

        ``keys`` (one per query) pins the stochasticity streams — row i then
        reproduces ``factorize(queries[i], keys[i])`` exactly.  Otherwise
        keys derive from ``key`` (or the engine's internal chain).

        ``priority`` orders the queue (lower serves first; FIFO within a
        priority).  ``max_iters`` caps this request's resonator iteration
        budget below ``cfg.max_iters`` — the fleet controller's brownout
        trim: rows retire at the cap with whatever estimate they reached.

        Queries and keys stay on the host until :meth:`_fill` slots them,
        as host copies: a device array is pulled once here, and the caller
        may reuse its buffers.
        """
        if max_iters is not None and max_iters < 1:
            raise ValueError(f"max_iters must be >= 1, got {max_iters}")
        queries = np.array(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        k = queries.shape[0]
        if keys is None:
            if key is None:
                self._key, key = jax.random.split(self._key)
            keys = jax.random.split(key, k)
        req = Request(self._next_id, queries, np.array(keys), meta,
                      self._clock(), self.sweeps_total,
                      priority=int(priority), iter_budget=max_iters)
        req.rows = [None] * k
        self._next_id += 1
        for qi in range(k):
            self._queue.append((req, qi))
        return req.id

    # -- serving loop ------------------------------------------------------

    def _pop_next(self):
        """Queue discipline: lowest ``(priority, id, qi)`` first.  Request
        ids are monotonic, so uniform priorities reduce to exact FIFO (the
        deque stays (id, qi)-sorted under appends and the front re-queues
        of resize/recover/preempt), and a re-queued row resumes ahead of
        same-priority newcomers."""
        best_i, best = 0, None
        for i, (req, qi) in enumerate(self._queue):
            k = (req.priority, req.id, qi)
            if best is None or k < best:
                best_i, best = i, k
        item = self._queue[best_i]
        del self._queue[best_i]
        return item

    def _fill(self) -> None:
        if not self._queue:
            return
        with self.obs.span("fill", track=self.obs_track, cat="engine") as sp:
            fills = []
            for slot in range(self.slots):
                if self._owner[slot] is not None or not self._queue:
                    continue
                req, qi = self._pop_next()
                self._owner[slot] = (req, qi)
                fills.append((slot, req, qi))
            if sp is not None:
                sp.args["rows"] = len(fills)
            if not fills:
                return
            # ONE fixed-shape jitted scatter for however many slots freed
            # up: indices pad with `slots` (out of range -> dropped), so
            # every fill count reuses the same compiled program.  Rows are
            # host arrays (see submit), copied into the padded host batch
            # with no device op per row; the batch goes up with the call.
            idx = np.full(self.slots, self.slots, np.int32)
            new_qs = np.zeros((self.slots, self.spec.dim), np.float32)
            keys0 = fills[0][1].keys
            keys = np.zeros((self.slots,) + keys0.shape[1:], keys0.dtype)
            for j, (slot, req, qi) in enumerate(fills):
                idx[j] = slot
                new_qs[j] = req.queries[qi]
                keys[j] = req.keys[qi]
            self.qs, self.state = self._refill_many(
                self.qs, self.state, idx, new_qs, keys)

    def _retire(self) -> list:
        """Retire ripe rows in three sibling spans: ``retire`` (the
        ``done``/``iters`` pulls and the choice of ripe rows), ``decode``
        (the batch decode, its pull and the per-row results) and one
        ``postprocess`` per finished request (:meth:`_finalize`)."""
        obs, track = self.obs, self.obs_track
        with obs.span("retire", track=track, cat="engine"):
            done = np.asarray(self.state.done)
            iters = np.asarray(self.state.iters)
            max_it = self.spec.cfg.max_iters

            def budget(req):
                # Per-request brownout trim: retire at the smaller cap.  The
                # device sweep still checks cfg.max_iters, so a trimmed row
                # is retired host-side at burst granularity (slight
                # overshoot, same as LM max_new_tokens trimming at burst
                # boundaries).
                b = req.iter_budget
                return max_it if b is None else min(max_it, b)

            ripe = [s for s in range(self.slots)
                    if self._owner[s] is not None
                    and (done[s] or iters[s] >= budget(self._owner[s][0]))]
        if not ripe:
            return []
        finished = []
        with obs.span("decode", track=track, cat="engine"):
            res = jax.device_get(self._decode(self.qs, self.state))
            for s in ripe:
                req, qi = self._owner[s]
                self._owner[s] = None
                req.rows[qi] = jax.tree.map(lambda a: a[s], res)
                if all(r is not None for r in req.rows):
                    finished.append(req)
        for req in finished:
            with obs.span("postprocess", track=track, cat="engine",
                          args={"queries": req.num_queries}
                          if obs.enabled else None):
                self._finalize(req)
        return finished

    def _finalize(self, req: Request) -> None:
        req.factorization = jax.tree.map(lambda *r: np.stack(r), *req.rows)
        req.iterations = req.factorization.iterations
        req.done_time = self._clock()
        req.done_sweep = self.sweeps_total
        req.result = req.factorization if self.spec.postprocess is None else \
            self.spec.postprocess(req.queries, req.factorization, req.meta)
        self.completed[req.id] = req
        self.completed_total += 1
        self._lat_sum += req.latency_s
        self._lat_window.append(req.latency_s)
        del self._lat_window[:-LAT_WINDOW_CAP]

    def step(self) -> list:
        """Fill free slots, run one adSCH-sized sweep burst, retire converged
        rows.  Returns the requests completed by this step.

        Traced, the ``step`` span holds the sibling phases ``fill``,
        ``sweep-burst``, ``retire``, ``decode`` and ``postprocess``."""
        obs = self.obs
        with obs.span("step", track=self.obs_track, cat="engine") as sp:
            self._fill()
            if all(o is None for o in self._owner):
                return []
            burst_args = None
            if obs.enabled:
                burst_args = {"live": sum(o is not None for o in self._owner),
                              "slots": self.slots}
            with obs.span("sweep-burst", track=self.obs_track,
                          cat="engine", args=burst_args) as bp:
                self.state, n = self._sweeps(self.qs, self.state,
                                             jnp.int32(self.sweeps_per_step))
                n = int(n)  # host sync: the burst span covers device time
            self.sweeps_total += n
            self.steps_total += 1
            finished = self._retire()
        if obs.enabled:
            bp.args["sweeps"] = n
            sp.args.update(sweeps=n, retired=len(finished))
        return finished

    def drain(self, max_steps: int = 100_000) -> list:
        """Run until every submitted request completed; returns them all
        (submission order)."""
        out = []
        for _ in range(max_steps):
            if not self._queue and all(o is None for o in self._owner):
                break
            out += self.step()
        else:
            raise RuntimeError("drain() exceeded max_steps")
        return sorted(out, key=lambda r: r.id)

    # -- online re-tuning --------------------------------------------------

    def resize(self, slots: int) -> None:
        """Warm handoff to a resized ``[slots, F, D]`` state (online re-tune).

        In-flight slot rows move into the new state verbatim — est / iters /
        done / sim / per-row PRNG keys travel as host copies of the exact
        device values — so a live request's remaining trajectory is the one
        it would have run in the old state (rows are independent; which slot
        index they occupy is irrelevant to the sweep math).  When shrinking
        below the live-row count, the overflow rows go back to the *front*
        of the queue and re-run from scratch once a slot frees: wasted
        sweeps, but still bit-equal — the per-request key pins the entire
        stochasticity stream, so a restarted row reproduces the same solo
        ``factorize(q, key)`` trajectory.

        Queued work is untouched.  The device programs are rebuilt at the new
        slot count (``_build_programs`` — the same seam ShardedEngine
        overrides, so a mesh engine re-tunes slots-per-shard identically) and
        the sweep burst is re-derived unless the constructor pinned it.
        """
        if slots < 1:
            raise ValueError(f"resize needs at least 1 slot, got {slots}")
        if slots == self.slots:
            return
        rsid = self.obs.begin("resize", track=self.obs_track, cat="engine",
                              args={"from": self.slots, "to": slots})
        live = [(s, self._owner[s]) for s in range(self.slots)
                if self._owner[s] is not None]
        keep, overflow = live[:slots], live[slots:]
        for _, owner in reversed(overflow):  # preserve original order up front
            self._queue.appendleft(owner)
        # Host snapshots BEFORE the rebuild replaces the device arrays.
        old_qs = np.asarray(self.qs)
        old_state = jax.tree.map(np.asarray, self.state)
        self.slots = slots
        if not self._sweeps_pinned:
            self.sweeps_per_step = self._derive_sweeps_per_step()
        self._build_programs()  # fresh parked state + programs (or shard_map)
        self._owner = [None] * slots
        if keep:
            rows = np.asarray([s for s, _ in keep])
            for j, (_, owner) in enumerate(keep):
                self._owner[j] = owner

            def carry(new, old):
                buf = np.asarray(new).copy()
                if buf.ndim and buf.shape[0] == slots:
                    buf[:len(rows)] = old[rows]
                    return jax.device_put(buf, new.sharding)
                return jax.device_put(old, new.sharding)  # global counters

            self.qs = carry(self.qs, old_qs)
            self.state = jax.tree.map(carry, self.state, old_state)
        else:
            self.state = self.state._replace(
                it=jax.device_put(old_state.it, self.state.it.sharding))
        self.resizes_total += 1
        self._step_cost_cache = None
        self.obs.end(rsid, args={"carried": len(keep),
                                 "requeued": len(overflow)})
        self.obs.count("resizes", 1, engine=self.obs_track)

    # -- fault tolerance ---------------------------------------------------

    def recover(self) -> int:
        """Rebuild after a fault and replay in-flight work; returns the
        number of replayed (request, query) rows.

        The device programs and slot state are rebuilt from scratch
        (``_build_programs`` — whatever the fault left behind, including
        non-finite resonator state, is discarded) and every live slot row
        goes back to the FRONT of the queue in its original submission
        order — the same bit-safe re-queue contract :meth:`resize` uses for
        shrink overflow.  A replayed row re-runs from its pinned per-query
        key, so its recovered trajectory is the solo ``factorize(q, key)``
        trajectory: bit-equal to a fault-free run, just later.  Queued work
        and already-retired rows are untouched.
        """
        with self.obs.span("recover", track=self.obs_track,
                           cat="engine") as sp:
            live = [(s, self._owner[s]) for s in range(self.slots)
                    if self._owner[s] is not None]
            for _, owner in reversed(live):  # submission order kept up front
                self._queue.appendleft(owner)
            self._build_programs()  # fresh parked state; corrupt state dropped
            self._owner = [None] * self.slots
            self.recoveries_total += 1
            if sp is not None:
                # the "recoveries" METRIC is supervision-scoped (counted by
                # the runtime's quarantine service, next to faults and
                # quarantines); the engine records only the span
                sp.args["replayed"] = len(live)
        return len(live)

    def preempt(self, request_id: int) -> int:
        """Bit-safe preemption: park ``request_id``'s live slot rows (the
        same ``done`` mask :meth:`cancel` uses) but RE-QUEUE the (request,
        query) owners at the front instead of discarding them — the
        re-queue-from-pinned-key contract :meth:`resize` shrink and
        :meth:`recover` use.  A preempted row re-runs from scratch off its
        pinned key once a slot frees, so its trajectory is bit-equal to an
        undisturbed run, just later.  Queued rows are untouched (they are
        already waiting).  Returns the number of rows re-queued.
        """
        parked = [s for s in range(self.slots)
                  if self._owner[s] is not None
                  and self._owner[s][0].id == request_id]
        if not parked:
            return 0
        for s in reversed(parked):  # keep row order at the queue front
            self._queue.appendleft(self._owner[s])
            self._owner[s] = None
        self.state = self.state._replace(
            done=self.state.done.at[jnp.asarray(parked)].set(True))
        self.obs.instant("preempt", track=self.obs_track, cat="engine",
                         args={"request": request_id, "rows": len(parked)})
        return len(parked)

    def cancel(self, request_id: int) -> bool:
        """Cancel request `request_id`: drop its queued rows and park its
        live slots (``done`` mask set, so the sweep freezes them and
        ``_fill`` treats them as free).  Slot reclamation only — other rows'
        trajectories are untouched (rows are independent; parking is the
        same mask the sweep itself uses to freeze converged rows).  Returns
        whether anything was reclaimed (False for unknown/completed ids).
        """
        before = len(self._queue)
        self._queue = deque((req, qi) for req, qi in self._queue
                            if req.id != request_id)
        reclaimed = len(self._queue) < before
        parked = [s for s in range(self.slots)
                  if self._owner[s] is not None
                  and self._owner[s][0].id == request_id]
        for s in parked:
            self._owner[s] = None
        if parked:
            self.state = self.state._replace(
                done=self.state.done.at[jnp.asarray(parked)].set(True))
        if reclaimed or parked:
            self.obs.instant("cancel", track=self.obs_track, cat="engine",
                             args={"request": request_id,
                                   "parked_slots": len(parked)})
        return reclaimed or bool(parked)

    def health_check(self) -> str | None:
        """Cadenced corruption probe: non-finite resonator state on any LIVE
        row (parked rows hold stale-but-finite values) is silent poison —
        scores and convergence sims go NaN, the row burns to ``max_iters``
        and decodes garbage.  Returns a description for the supervisor to
        quarantine on, or None when healthy."""
        live = [s for s in range(self.slots) if self._owner[s] is not None]
        if not live:
            return None
        est = np.asarray(self.state.est[jnp.asarray(live)])
        bad = [live[i] for i in range(len(live))
               if not np.isfinite(est[i]).all()]
        if bad:
            return f"non-finite resonator state in slot rows {bad}"
        return None

    # -- introspection -----------------------------------------------------

    @property
    def in_flight(self) -> int:
        return sum(o is not None for o in self._owner) + len(self._queue)

    def live_requests(self) -> dict:
        """``{request_id: {"priority": p, "rows": n}}`` for slotted rows —
        the fleet controller's preemption-victim view."""
        out: dict = {}
        for o in self._owner:
            if o is not None:
                d = out.setdefault(o[0].id,
                                   {"priority": o[0].priority, "rows": 0})
                d["rows"] += 1
        return out

    def queued_requests(self) -> dict:
        """``{request_id: {"priority": p, "rows": n}}`` for queued rows."""
        out: dict = {}
        for req, _ in self._queue:
            d = out.setdefault(req.id,
                               {"priority": req.priority, "rows": 0})
            d["rows"] += 1
        return out

    def step_cost_s(self) -> float:
        """adSCH-modeled wall seconds of one ``step()`` burst (used by the
        runtime's cost-weighted engine picking).  Cached — the inputs only
        change on :meth:`resize`, and the runtime asks after every step."""
        if self._step_cost_cache is None:
            shards = getattr(self, "data_shards", 1), (
                self.model_shards if getattr(self, "_rows", False) else 1)
            ops = step_unit_ops(self.spec, self.slots, data_shards=shards[0],
                                model_shards=shards[1])
            t_unit = sch.schedule(ops, self.hw).makespan / self.hw.freq_hz
            self._step_cost_cache = self.sweeps_per_step * t_unit
        return self._step_cost_cache

    def snapshot(self, reset: bool = False) -> dict:
        """Unified-schema counters + rolling latency percentiles.

        The common keys every engine kind reports (see DESIGN.md
        "Observability"): ``engine_kind``, ``slots``, ``units_per_step`` /
        ``units_total`` (one *unit* is this engine's step atom — a resonator
        sweep here, a decode token for the LM adapter), ``steps``,
        ``completed``, ``resizes``, ``recoveries``, and the rolling window
        percentiles with ``window_completed``.  Engine-specific aliases
        (``sweeps_per_step``/``sweeps_total``) ride along.

        ``reset=False`` (the default) is NON-destructive: concurrent
        readers — the Runtime's stats merge, a metrics scrape, a debugging
        print — all see the same window.  ``reset=True`` drains the rolling
        latency window (the read-and-reset semantics :meth:`stats` keeps for
        interval-over-interval reporting); totals always keep accumulating
        (tracked incrementally, so evicting ``completed`` entries does not
        distort them).
        """
        lats = self._lat_window
        if reset:
            self._lat_window = []
        return {
            "engine_kind": self.engine_kind,
            "slots": self.slots,
            "units_per_step": self.sweeps_per_step,
            "units_total": self.sweeps_total,
            "sweeps_per_step": self.sweeps_per_step,
            "steps": self.steps_total,
            "sweeps_total": self.sweeps_total,
            "completed": self.completed_total,
            "resizes": self.resizes_total,
            "recoveries": self.recoveries_total,
            "window_completed": len(lats),
            **rolling_latency_ms(lats),
            "latency_mean_all_ms": (self._lat_sum / self.completed_total * 1e3
                                    if self.completed_total else None),
        }

    def stats(self) -> dict:
        """Read-and-reset snapshot (the original destructive window
        semantics).  Prefer :meth:`snapshot` when more than one reader
        exists — two ``stats()`` callers race and each sees half the
        window."""
        return self.snapshot(reset=True)
