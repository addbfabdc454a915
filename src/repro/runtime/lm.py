"""LMEngine: transformer serving behind the engine-style Steppable API.

One class holds every LM request and slot: the queue, slot ownership, each
slot's KV length, active flag and next input token, retirement, resize,
recovery, preemption, counters and spans, so one
:class:`repro.runtime.Runtime` can interleave LM decode with symbolic
factorization engines.  The device side is one of the two KV layouts of
:mod:`repro.lm.kv`, chosen once at construction: the block-table pool
(:class:`~repro.lm.kv.PagedKV`) when ``paged`` is a :class:`PagedConfig`
or the stack has latent attention, else the contiguous cache
(:class:`~repro.lm.kv.ContiguousKV`).  On the pool :meth:`resize` is a
block-table edit, so the Runtime's EWMA re-tuner warm-hands-off the LM
engine exactly like the factorizer engines (in-flight slots carried
bit-equal).  On the contiguous cache :meth:`resize` replays: live requests
re-queue from their pinned prompts (deterministic decode makes the replayed
tokens bit-equal, the ``recover()`` argument).

The adSCH connection runs through the registered ``lm_decode`` spec
(:mod:`repro.engine.pipelines`): its StageGraph declares prefill as the
neural block and per-token decode as the sliver-filling stream, and its
``step_ops`` price one decode token over the slot batch — so the SAME
:func:`repro.engine.engine.derive_sweeps_per_step` that sizes resonator
sweep bursts sizes the decode burst between retirement scans here
(``decode_per_step``), and :func:`plan_interleave` prices the
prefill/decode boundary like any other stage boundary.

Retirement is at burst granularity (like the factorizer engine's sweep
bursts): a slot may overshoot its stop condition by up to
``decode_per_step - 1`` tokens; the finished request's ``tokens`` are
trimmed to ``max_new_tokens`` / first EOS (``logits`` holds each kept
token's f32 logit), and a slot parked at KV capacity retires with
``truncated=True``.  On the paged pool a request is admitted only when the
blocks it can reach (prompt, ``max_new_tokens`` and one burst's overshoot)
are free beyond those the live slots may still claim, so a pool that admits
never parks a slot mid-generation.  ``sweeps_total`` counts decode steps
over the slot batch, the LM's counterpart of a resonator sweep.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.cogsim import model as hw_model
from repro.core import scheduler as sch
from repro.engine import registry
from repro.engine.engine import (LAT_WINDOW_CAP, derive_sweeps_per_step,
                                 rolling_latency_ms, step_unit_ops)
from repro.lm import sampling as lm_sampling
from repro.lm.kv import ContiguousKV, PagedKV
from repro.lm.paging import PagedConfig
from repro.lm.sampling import SamplingSpec
from repro.nn import transformer as T


@dataclasses.dataclass
class LMRequest:
    """One submitted generation request."""

    id: int
    prompt: Any  # [T] int32 tokens
    max_new_tokens: int
    meta: Any
    submit_time: float
    sampling: SamplingSpec | None = None  # None = greedy
    priority: int = 0  # queue order: lower serves first (fleet classes)
    tokens: list = dataclasses.field(default_factory=list)  # generated ids
    logits: list = dataclasses.field(default_factory=list)  # their f32 logits
    result: Any = None  # {"tokens": ..., "text_len": ...} convenience dict
    truncated: bool = False  # KV capacity parked the slot before a stop
    done_time: float | None = None

    @property
    def latency_s(self) -> float | None:
        return None if self.done_time is None else \
            self.done_time - self.submit_time


def _carry(a: np.ndarray, rows: list, n: int) -> np.ndarray:
    """``a``'s entries at ``rows`` in rows 0.., zero-padded to ``n``."""
    out = np.zeros(n, a.dtype)
    out[:len(rows)] = a[rows]
    return out


class LMEngine:
    """``submit()/step()/drain()`` continuous batching over a KV layout.

    Satisfies :class:`repro.runtime.protocol.Steppable`; requests are token
    prompts instead of query vectors, results are generated token lists.
    """

    engine_kind = "lm"  # unified stats schema discriminator

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 128,
                 prompt_len_hint: int = 16, decode_per_step: int | None = None,
                 eos_id: int | None = None, paged: PagedConfig | None = None,
                 hw=hw_model.COGSYS, obs=None, clock=None):
        if paged is not None and not isinstance(paged, PagedConfig):
            raise TypeError(
                f"paged= expects a PagedConfig or None, got {paged!r}")
        if paged is None and T.contiguous_unsupported_reason(cfg) is not None:
            paged = PagedConfig()  # latent stacks serve paged only
        self._layout = functools.partial(ContiguousKV, cfg) if paged is None \
            else functools.partial(PagedKV, cfg, paged=paged)
        self.cfg, self.hw = cfg, hw
        self.slots = slots
        self.eos_id = eos_id
        self._prompt_len_hint = prompt_len_hint
        self._dps_pinned = decode_per_step is not None
        # Observability seam, mirroring Engine: spans/counters around the
        # device dispatches, NULL default, one clock (see Engine.bind_obs).
        self.obs = obs if obs is not None else obs_mod.NULL
        self.obs_track = "lm"
        self._default_clock = clock is None
        self._clock = clock if clock is not None else self.obs.clock
        # kept for fault recovery: recover() rebuilds the device layer from
        # these (params are read-only serving state, never mutated by decode)
        self._params, self._max_len = params, max_len
        self.serve = self._layout(params, slots, max_len)
        self.spec = self._build_spec(slots)
        self.decode_per_step = (
            derive_sweeps_per_step(self.spec, slots, hw)
            if decode_per_step is None else decode_per_step)
        self._clear_slots()
        self._queue: deque = deque()
        self._next_id = 0
        self.completed: dict = {}
        self.completed_total = 0  # all-time (runtime may evict `completed`)
        self.steps_total = 0
        self.sweeps_total = 0  # decode steps over the slot batch
        self.tokens_total = 0
        self.recoveries_total = 0
        self.resizes_total = 0
        # structural serving metrics (interpret-mode wall time is not the
        # signal; these are): dispatches and modeled KV bytes per decode
        self.prefill_dispatches = 0
        self.decode_dispatches = 0
        self.kv_bytes_touched = 0
        # token-expert picks of live decode rows on the experts held here
        # (expert-share MoE stacks; pulled with each step's tokens), and
        # the (layer, held expert) pairs they fall on
        self.held_picks_total = 0
        moe = cfg.moe if cfg.moe is not None and cfg.moe.held else None
        self.held_experts = 0 if moe is None else moe.held * cfg.n_periods \
            * sum(k == "attn_moe" for k in cfg.block_pattern)
        # [slots, 1, vocab] fp32 logits of the latest decode step (None
        # before the first): what layouts and kernels are compared on
        self.last_logits = None
        self._lat_sum = 0.0
        self._lat_window: list = []
        self._step_cost = self._modeled_step_cost()
        self._record_structure()

    def _clear_slots(self) -> None:
        """Every slot free, with the host mirror of its KV: ``lens`` (the
        positions written; a decode step writes at ``len``), ``active``
        (decoding: owned and not parked at KV capacity) and the token the
        next decode step feeds."""
        self._owner: list = [None] * self.slots  # LMRequest | None
        self.active = np.zeros(self.slots, bool)
        self.lens = np.zeros(self.slots, np.int64)
        self._last = np.zeros(self.slots, np.int32)

    def _record_structure(self) -> None:
        if not self.obs.enabled:
            return
        track = self.obs_track
        self.obs.gauge("slots", self.slots, engine=track)
        self.obs.gauge("units_per_step", self.decode_per_step, engine=track)
        self.obs.gauge("paged", int(self.serve.paged), engine=track)

    def bind_obs(self, obs, track: str | None = None) -> None:
        """Adopt a recorder after construction (see ``Engine.bind_obs``)."""
        self.obs = obs
        if track is not None:
            self.obs_track = track
        if self._default_clock:
            self._clock = obs.clock
        self._record_structure()

    def _build_spec(self, slots: int):
        return registry.build(
            "lm_decode", None, cfg=self.cfg, batch=slots,
            prompt_len=self._prompt_len_hint, max_len=self._max_len,
            kv_block=self.serve.kv_block)

    def _modeled_step_cost(self) -> float:
        ops = step_unit_ops(self.spec, self.slots)
        return self.decode_per_step * (
            sch.schedule(ops, self.hw).makespan / self.hw.freq_hz)

    # -- request intake ----------------------------------------------------

    def submit(self, prompt, *, max_new_tokens: int = 32, meta=None,
               sampling: SamplingSpec | None = None,
               priority: int = 0) -> int:
        """Enqueue one prompt; returns the request id.  Prompts that cannot
        fit the KV capacity at all are rejected here (the per-token guard
        then parks slots that fill up mid-generation).  ``sampling`` picks
        temperature/top-k decoding for this request (None = greedy); the
        per-request seed makes replay after recover/resize bit-equal.
        ``priority`` orders the queue (lower serves first; FIFO within a
        priority)."""
        # host-side ids: slicing a device array of a new length would
        # compile a program per prompt length
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.shape[0] == 0:
            raise ValueError("submit expects a non-empty 1-D token prompt")
        # prompt[:-1] prefills and the last token still needs a KV position
        # on the first decode step: len(prompt) positions in all
        if prompt.shape[0] > self.serve.capacity:
            raise ValueError(
                f"prompt of {prompt.shape[0]} tokens exceeds the engine's "
                f"KV capacity {self.serve.capacity}")
        if sampling is not None and not isinstance(sampling, SamplingSpec):
            raise TypeError(
                f"sampling= expects a SamplingSpec or None, got {sampling!r}")
        req = LMRequest(self._next_id, prompt, int(max_new_tokens), meta,
                        self._clock(), sampling=sampling,
                        priority=int(priority))
        self._next_id += 1
        self._queue.append(req)
        self.obs.count("submitted", 1, engine=self.obs_track)
        return req.id

    # -- serving loop ------------------------------------------------------

    def _next_index(self) -> int:
        """Queue discipline: lowest ``(priority, id)`` first.  Request ids
        are monotonic, so uniform priorities reduce to exact FIFO."""
        best_i, best = 0, None
        for i, req in enumerate(self._queue):
            k = (req.priority, req.id)
            if best is None or k < best:
                best_i, best = i, k
        return best_i

    def _reach(self, req: LMRequest) -> int:
        """Positions a request can grow to: its prompt, every token it may
        generate and one burst's overshoot."""
        return len(req.prompt) + req.max_new_tokens + self.decode_per_step

    def _fill(self) -> None:
        serve = self.serve
        for slot in range(self.slots):
            if self._owner[slot] is not None or not self._queue:
                continue
            i = self._next_index()
            req = self._queue[i]
            n = len(req.prompt)
            # paged: a drained pool defers admission (priority order
            # preserved — the BEST candidate parks) until retiring slots
            # release blocks — parking, not rejection
            live = ((s, self._reach(r)) for s, r in enumerate(self._owner)
                    if r is not None)
            if not serve.can_admit(n, serve.claims(live, n,
                                                   self._reach(req))):
                break
            del self._queue[i]
            self._owner[slot] = req
            req.tokens, req.logits = [], []  # a replay starts afresh
            _, dispatches = serve.prefill(slot, req.prompt, self.obs,
                                          self.obs_track)
            self.prefill_dispatches += dispatches
            if self.obs.enabled and dispatches:
                self.obs.count("prefill_dispatches", dispatches,
                               engine=self.obs_track)
            self.active[slot] = True
            self.lens[slot] = n - 1
            self._last[slot] = req.prompt[-1]

    def _decode(self) -> bool:
        """One masked decode step over the slot batch.  Slots with no KV
        room for this step's write park first, in ascending slot order
        (deterministic under replay); a parked slot keeps its KV until it
        retires.  Returns False when nothing is left to decode."""
        serve = self.serve
        for s in np.flatnonzero(self.active):
            if not serve.grow(s, int(self.lens[s]) + 1):
                self.active[s] = False
        if not self.active.any():
            return False
        logits, top, top_logit, held = serve.decode(
            self._last[:, None], self.lens, self.active)
        self.last_logits = logits
        self.decode_dispatches += 1
        kv_bytes = serve.kv_step_bytes(self.lens)
        self.kv_bytes_touched += kv_bytes
        if self.obs.enabled:
            self.obs.count("decode_dispatches", 1, engine=self.obs_track)
            self.obs.count("kv_bytes_touched", kv_bytes,
                           engine=self.obs_track)
        self.lens[self.active] += 1
        nxt, nxt_logit, held = jax.device_get((top, top_logit, held))
        nxt, nxt_logit = np.array(nxt), np.array(nxt_logit)
        self.held_picks_total += int(held)
        live = np.flatnonzero(self.active)
        sampled = False
        for s in live:
            spec = self._owner[s].sampling
            if spec is not None:
                nxt[s] = lm_sampling.sample_token(logits[s, -1], spec,
                                                  int(self.lens[s]))
                sampled = True
        if sampled:  # the chosen tokens' logits, in one gather and pull
            nxt_logit = np.array(jnp.take_along_axis(
                logits[:, -1], jnp.asarray(nxt, jnp.int32)[:, None], -1)[:, 0])
        for s in live:
            req = self._owner[s]
            req.tokens.append(int(nxt[s]))
            req.logits.append(float(nxt_logit[s]))
        self._last[live] = nxt[live]
        return True

    def _stop_at(self, req: LMRequest, produced: list) -> int | None:
        """Index (exclusive) to trim `produced` at, or None if not done."""
        if self.eos_id is not None and self.eos_id in produced:
            return min(produced.index(self.eos_id) + 1, req.max_new_tokens)
        if len(produced) >= req.max_new_tokens:
            return req.max_new_tokens
        return None

    def _release(self, slot: int) -> None:
        """Free a slot (paged: its blocks go back to the pool)."""
        self._owner[slot] = None
        self.active[slot] = False
        self.serve.release(slot)

    def _retire(self) -> list:
        finished = []
        for slot, req in enumerate(self._owner):
            if req is None:
                continue
            stop = self._stop_at(req, req.tokens)
            if stop is None and self.active[slot]:
                continue
            req.truncated = stop is None  # parked at KV capacity
            req.tokens = req.tokens[:stop]
            req.logits = req.logits[:len(req.tokens)]
            req.done_time = self._clock()
            req.result = {"tokens": req.tokens, "logits": req.logits,
                          "truncated": req.truncated}
            self.tokens_total += len(req.tokens)
            self.completed[req.id] = req
            self.completed_total += 1
            self._lat_sum += req.latency_s
            self._lat_window.append(req.latency_s)
            del self._lat_window[:-LAT_WINDOW_CAP]
            self._release(slot)
            finished.append(req)
        return finished

    def step(self) -> list:
        """Fill free slots (prefill), run one adSCH-sized decode burst,
        retire finished slots.  Returns the requests completed this step."""
        obs = self.obs
        with obs.span("step", track=self.obs_track, cat="engine") as sp:
            with obs.span("fill", track=self.obs_track, cat="engine"):
                self._fill()
            live = sum(o is not None for o in self._owner)
            if not live:
                return []
            held0 = self.held_picks_total
            with obs.span("decode-burst", track=self.obs_track,
                          cat="engine") as bp:
                n = kv_tokens = 0
                for _ in range(self.decode_per_step):
                    # every live slot parked at capacity ends the burst early
                    if not self._decode():
                        break
                    n += 1
                    if obs.enabled:  # cached positions this step read
                        kv_tokens += int(self.lens[self.active].sum())
            self.steps_total += 1
            self.sweeps_total += n
            with obs.span("retire", track=self.obs_track, cat="engine"):
                finished = self._retire()
        if obs.enabled:
            bp.args.update(live=live, slots=self.slots, steps=n,
                           kv_tokens=kv_tokens)
            if self.held_experts:
                bp.args.update(held_picks=self.held_picks_total - held0,
                               held_experts=self.held_experts)
            sp.args.update(decodes=n, retired=len(finished))
            obs.count("steps", 1, engine=self.obs_track)
            obs.count("decode_steps", n, engine=self.obs_track)
            if finished:
                obs.count("completed", len(finished), engine=self.obs_track)
                obs.count("tokens",
                          sum(len(r.tokens) for r in finished),
                          engine=self.obs_track)
        return finished

    def drain(self, max_steps: int = 100_000) -> list:
        out = []
        for _ in range(max_steps):
            if not self._queue and all(o is None for o in self._owner):
                break
            out += self.step()
        else:
            raise RuntimeError("drain() exceeded max_steps")
        return sorted(out, key=lambda r: r.id)

    # -- warm handoff ------------------------------------------------------

    def resize(self, new_slots: int) -> None:
        """Re-tune the slot count mid-run (the Runtime's EWMA re-tuner calls
        this through the same ``Engine.resize`` contract as the factorizer
        engines).

        Paged: a block-table edit — the first ``new_slots`` live requests
        keep their physical KV blocks and host state verbatim (bit-equal
        trajectories across the resize); displaced live requests re-queue
        at the FRONT in slot order and replay from their pinned prompts.
        Contiguous: the cache cannot re-slot without a reshape, so EVERY
        live request replays (deterministic greedy / seeded sampling makes
        the regenerated tokens bit-equal — the ``recover()`` argument).
        """
        if new_slots < 1:
            raise ValueError(f"resize needs >= 1 slot, got {new_slots}")
        if new_slots == self.slots:
            return
        rsid = self.obs.begin("resize", track=self.obs_track, cat="engine",
                              args={"from": self.slots, "to": new_slots})
        live = [(s, r) for s, r in enumerate(self._owner) if r is not None]
        keep = live[:new_slots] if self.serve.paged else []
        overflow = live[len(keep):]
        for _, req in reversed(overflow):
            self._queue.appendleft(req)
        rows = [s for s, _ in keep]
        self.serve.resize(new_slots, rows)
        self._owner = [req for _, req in keep] + \
            [None] * (new_slots - len(keep))
        self.active, self.lens, self._last = (
            _carry(a, rows, new_slots)
            for a in (self.active, self.lens, self._last))
        self.slots = new_slots
        self.spec = self._build_spec(new_slots)
        if not self._dps_pinned:
            self.decode_per_step = derive_sweeps_per_step(
                self.spec, new_slots, self.hw)
        self._step_cost = self._modeled_step_cost()
        self.resizes_total += 1
        self._record_structure()
        self.obs.end(rsid, args={"carried": len(keep),
                                 "requeued": len(overflow)})
        self.obs.count("resizes", 1, engine=self.obs_track)

    # -- fault tolerance ---------------------------------------------------

    def recover(self) -> int:
        """Rebuild the device layer after a fault and replay in-flight
        generations; returns the number of replayed requests.

        A fresh KV layout replaces the (possibly corrupt) cache or pool and
        every slot is freed; live requests re-queue at the FRONT in
        submission order and re-run prefill + decode from their pinned
        prompts.  Greedy decode is deterministic and sampled requests
        re-derive their keys from (seed, position), so a replayed request's
        tokens are bit-equal to a fault-free run — partially generated
        tokens are simply regenerated.
        """
        with self.obs.span("recover", track=self.obs_track,
                           cat="engine") as sp:
            live = [req for req in self._owner if req is not None]
            for req in reversed(live):
                self._queue.appendleft(req)
            self.serve = self._layout(self._params, self.slots,
                                      self._max_len)
            self._clear_slots()
            self.recoveries_total += 1
            if sp is not None:
                # "recoveries" as a metric is supervision-scoped (counted by
                # the runtime's quarantine service); the engine keeps the span
                sp.args["replayed"] = len(live)
        return len(live)

    def preempt(self, request_id: int) -> int:
        """Bit-safe preemption: free the request's slot (the device layer
        stops decoding it and, when paged, returns its KV blocks to the
        pool) and RE-QUEUE it at the front — the :meth:`recover` contract.
        On re-fill it prefills from scratch; deterministic greedy decoding
        (and the per-request sampling seed) regenerates the same token
        stream, so the replayed stream is bit-equal to an undisturbed run,
        just later.  Queued requests are untouched.  Returns 1 when a live
        slot was preempted, else 0.
        """
        for slot, req in enumerate(self._owner):
            if req is not None and req.id == request_id:
                self._release(slot)
                self._queue.appendleft(req)
                self.obs.instant("preempt", track=self.obs_track,
                                 cat="engine",
                                 args={"request": request_id, "rows": 1})
                return 1
        return 0

    def cancel(self, request_id: int) -> bool:
        """Cancel one request: drop it from the queue or free its slot
        (the device layer stops decoding it and, when paged, returns its
        KV blocks to the pool).  Work is discarded — see :meth:`preempt`
        for the bit-safe re-queue flavor.  Returns whether anything was
        reclaimed.
        """
        for i, req in enumerate(self._queue):
            if req.id == request_id:
                del self._queue[i]
                return True
        for slot, req in enumerate(self._owner):
            if req is not None and req.id == request_id:
                self._release(slot)
                return True
        return False

    # -- introspection -----------------------------------------------------

    @property
    def in_flight(self) -> int:
        return sum(o is not None for o in self._owner) + len(self._queue)

    def live_requests(self) -> dict:
        """``{request_id: {"priority": p, "rows": 1}}`` for slotted requests
        — the fleet controller's preemption-victim view."""
        return {req.id: {"priority": req.priority, "rows": 1}
                for req in self._owner if req is not None}

    def queued_requests(self) -> dict:
        """``{request_id: {"priority": p, "rows": 1}}`` for queued requests."""
        return {req.id: {"priority": req.priority, "rows": 1}
                for req in self._queue}

    def step_cost_s(self) -> float:
        return self._step_cost

    def snapshot(self, reset: bool = False) -> dict:
        """Unified-schema counters (see ``Engine.snapshot``: a *unit* here
        is one generated decode token).  ``reset=False`` is non-destructive;
        ``reset=True`` drains the rolling latency window.  LM-specific keys
        (``decode_per_step``/``tokens_total``, dispatch + KV-byte structural
        counters) ride along."""
        lats = self._lat_window
        if reset:
            self._lat_window = []
        return {
            "engine_kind": self.engine_kind,
            "slots": self.slots,
            "units_per_step": self.decode_per_step,
            "units_total": self.tokens_total,
            "decode_per_step": self.decode_per_step,
            "paged": self.serve.paged,
            "steps": self.steps_total,
            "completed": self.completed_total,
            "tokens_total": self.tokens_total,
            "recoveries": self.recoveries_total,
            "resizes": self.resizes_total,
            "prefill_dispatches": self.prefill_dispatches,
            "decode_dispatches": self.decode_dispatches,
            "kv_bytes_touched": self.kv_bytes_touched,
            "window_completed": len(lats),
            **rolling_latency_ms(lats),
            "latency_mean_all_ms": (self._lat_sum / self.completed_total * 1e3
                                    if self.completed_total else None),
        }

    def stats(self) -> dict:
        """Read-and-reset snapshot (see ``Engine.stats``)."""
        return self.snapshot(reset=True)
