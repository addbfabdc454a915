"""Serving driver: batched prefill + decode with request slotting.

The CogSys system-level insight (adSCH interleaving, Sec. VI) maps to LM
serving as continuous batching: new requests are slotted into the fixed
decode batch as old ones finish, so the heterogeneous prefill/decode kernels
keep the array busy — the same utilization argument as Fig. 13b.

Two device layouts behind one API:

  * contiguous (default): one ``[periods, slots, max_len, ...]`` KV cache,
    per-token prefill — the original path, kept for stateful block kinds
    (mamba / xLSTM) the paged layout doesn't cover;
  * paged (``paged=PagedConfig(...)``): a shared block pool + per-slot
    block tables (:mod:`repro.lm.paging`), chunked prefill (one dispatch
    per ``prefill_chunk`` tokens instead of one per token), flash-decode
    attention (:mod:`repro.kernels.flash_decode`), capacity limited by the
    pool instead of ``max_len``, and ``resize()`` as a block-table edit.
    Latent-attention (MLA) stacks serve here only.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --smoke
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.configs.registry import ARCHS
from repro.launch.programs import enable_compile_cache
from repro.lm import model as lm_model
from repro.lm import sampling as lm_sampling
from repro.lm.paging import BlockTablePool, PagedConfig, cdiv
from repro.nn import transformer as T

log = logging.getLogger(__name__)


def _greedy(logits):
    """(argmax token [B] int32, its logit [B] f32) of the last position."""
    last = logits[:, -1]
    return jnp.argmax(last, axis=-1).astype(jnp.int32), jnp.max(last, axis=-1)


class ServeEngine:
    """Static-batch continuous batching over a shared KV cache."""

    def __init__(self, cfg, params, batch_slots: int, max_len: int,
                 paged: PagedConfig | None = None, obs=None,
                 obs_track: str = "lm"):
        if paged is not None and not isinstance(paged, PagedConfig):
            # catch the natural misuse paged=True before it dies as an
            # opaque AttributeError inside a jit trace (same guard as the
            # resonator FusedConfig)
            raise TypeError(
                f"paged= expects a PagedConfig or None, got {paged!r}")
        self.cfg, self.params = cfg, params
        self.max_len = max_len
        self.slots = batch_slots
        self.paged = paged
        # Observability seam (see repro.obs): spans/counters recorded around
        # the jitted dispatches; the NULL default costs one attribute read.
        self.obs = obs if obs is not None else obs_mod.NULL
        self.obs_track = obs_track
        self.active = np.zeros(batch_slots, bool)
        self.generated: list = [[] for _ in range(batch_slots)]
        # f32 logit of each generated token (generated[s][1:]), pulled in
        # the same transfer as the token ids
        self.generated_logits: list = [[] for _ in range(batch_slots)]
        # Host mirror of each slot's KV length + capacity parking flags: a
        # decode step writes KV at position len, so a slot out of KV room
        # must NOT step again.  step() parks such slots (active=False,
        # overflowed=True) instead.
        self.lens = np.zeros(batch_slots, np.int64)
        self.overflowed = np.zeros(batch_slots, bool)
        # Per-slot sampling override (None = the step()-level sampler args,
        # greedy by default); set by add_request(sampling=...).
        self.sampling: list = [None] * batch_slots
        # Structural serving metrics (interpret-mode wall time is not the
        # signal; these are): dispatches and modeled KV bytes per decode.
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
        if paged is not None:
            lm_model.check_paging_supported(cfg)
            nb = paged.resolve_num_blocks(batch_slots, max_len)
            width = paged.resolve_table_width(batch_slots, max_len)
            self.blocks = BlockTablePool(nb, paged.block_size, batch_slots,
                                         width)
            self.pool = lm_model.init_pool(cfg, nb, paged.block_size)
            # The pool is donated through every dispatch (it is THE mutable
            # serving state); closures carry no batch dim, so resize() is
            # pure host-side re-slotting + an automatic shape recompile.
            # the greedy pick and its logit ride in the decode program, so
            # one pull per step brings tokens, logits and counters back
            def decode(p, pool, table, lens, tok, act):
                logits, pool, held = lm_model.decode_step_paged(
                    p, cfg, pool, table, lens, tok, act,
                    use_flash=paged.use_flash, interpret=paged.interpret)
                return (logits, pool, *_greedy(logits),
                        jnp.sum(jnp.where(act, held, 0)))

            self._decode_paged = jax.jit(decode, donate_argnums=(1,))
            self._prefill_paged = jax.jit(
                lambda p, pool, row_table, len0, tok, count:
                lm_model.prefill_chunk_paged(p, cfg, pool, row_table, len0,
                                             tok, count),
                donate_argnums=(1,))
            return
        self.cache = T.init_cache(cfg, batch_slots, max_len)

        # One decode step with the active-slot select fused into the jitted
        # program: inactive slots keep their old cache rows (their dummy
        # token must not advance the KV length a later add_request prefills
        # against), and no eager full-cache copy happens per token.  Every
        # cache leaf is [periods, batch, ...] (see T.init_cache).
        def decode_masked(p, c, tok, act):
            logits, new = T.decode_step(p, cfg, c, tok)
            merged = jax.tree.map(
                lambda o, n: jnp.where(
                    act.reshape((1, batch_slots) + (1,) * (o.ndim - 2)), n, o),
                c, new)
            return logits, merged

        # the greedy pick and its logit ride in the program, as on the pool
        def decode_greedy(p, c, tok, act):
            logits, merged = decode_masked(p, c, tok, act)
            return (logits, merged, *_greedy(logits))

        self._decode = jax.jit(decode_greedy)
        # Prefill one token into ONE slot: decode the whole (static-shape)
        # batch but write back only the target slot's row.
        self._prefill = jax.jit(lambda p, c, tok, slot: decode_masked(
            p, c, jnp.broadcast_to(tok, (batch_slots, 1)).astype(jnp.int32),
            jnp.arange(batch_slots) == slot))
        # Pristine per-slot state for slot reuse (xLSTM stabilizer rows init
        # to -1e9, so "reset" must slice from a fresh cache, not zero).
        self._fresh_cache = T.init_cache(cfg, batch_slots, max_len)
        # Slot reset as ONE jitted dispatch with the stale cache donated:
        # only the target row of each leaf is rewritten in place.  The old
        # eager tree.map of `.at[:, slot].set` copied every full leaf per
        # admission — O(cache), not O(row).
        self._reset_slot = jax.jit(
            lambda c, f, slot: jax.tree.map(
                lambda cl, fl: cl.at[:, slot].set(jnp.take(fl, slot, axis=1)),
                c, f),
            donate_argnums=(0,))

    # -- capacity ----------------------------------------------------------

    @property
    def slot_capacity(self) -> int:
        """Max tokens one slot can hold (cache row / block-table width)."""
        if self.paged is None:
            return self.max_len
        return self.blocks.slot_capacity

    def can_admit(self, tokens: int, reserved: int = 0) -> bool:
        """Whether a fresh ``tokens``-token prompt can be admitted NOW
        (paged: enough free blocks beyond ``reserved`` of them;
        contiguous: fits the row)."""
        if tokens > self.slot_capacity:
            return False
        if self.paged is None:
            return True
        return self.blocks.free_blocks - reserved >= \
            cdiv(tokens, self.paged.block_size)

    def _kv_step_bytes(self) -> int:
        """Modeled KV bytes one decode dispatch reads (all attn layers)."""
        cfg = self.cfg
        G = cfg.n_kv_heads
        dh = cfg.head_dim if cfg.head_dim is not None else \
            cfg.d_model // cfg.n_heads
        int8 = cfg.kv_cache_dtype == "int8"
        per_tok = 2 * G * dh * (1 if int8 else 2) + (2 * G * 4 if int8 else 0)
        if cfg.mla is not None:  # one bf16 latent entry serves every head
            per_tok = 2 * cfg.mla.latent_dim
        n_attn = sum(k.startswith("attn") for k in cfg.block_pattern) \
            * cfg.n_periods + cfg.first_dense
        if self.paged is None:
            window = self.slots * self.max_len  # dense read of the full cache
        elif self.paged.use_flash:
            bs = self.paged.block_size  # ceil(len/bs) block gathers per row
            window = sum(cdiv(int(l) + 1, bs) * bs for l in self.lens)
        else:  # dense gathered reference reads each row's full table window
            window = self.slots * self.blocks.table_width \
                * self.paged.block_size
        return window * per_tok * n_attn

    # -- admission ---------------------------------------------------------

    def release_slot(self, slot: int) -> None:
        """Stop serving a slot and (paged) return its blocks to the pool."""
        self.active[slot] = False
        self.sampling[slot] = None
        self.generated_logits[slot] = []
        if self.paged is not None:
            self.blocks.release(slot)

    def add_request(self, slot: int, prompt: jnp.ndarray, sampling=None):
        """Prefill a prompt into one slot.

        The slot's prior state is released first (slots are reused across
        requests).  Only ``prompt[:-1]`` is prefilled; the last prompt token
        is seeded into ``generated`` so the next ``step()`` feeds it —
        writing its KV exactly once and producing the true first next-token
        logits.  ``sampling`` (a :class:`repro.lm.sampling.SamplingSpec`)
        overrides the engine-level sampler for this slot.  Returns the
        target slot's logits after the last *prefilled* token (``None`` for
        prompts shorter than 2 tokens).
        """
        if prompt.shape[0] == 0:  # nothing to serve; leave the slot parked
            return None
        n = int(prompt.shape[0])
        if n > self.slot_capacity:
            # prompt[:-1] prefills and the seeded last token still needs a KV
            # position on the first step(): len(prompt) rows of cache total
            raise ValueError(
                f"prompt of {n} tokens exceeds the cache capacity "
                f"{self.slot_capacity}"
                + ("" if self.paged is not None else
                   f" (max_len={self.max_len})"))
        if sampling is not None and \
                not isinstance(sampling, lm_sampling.SamplingSpec):
            raise TypeError(f"sampling= expects a SamplingSpec or None, "
                            f"got {sampling!r}")
        logits = None
        disp0 = self.prefill_dispatches
        if self.paged is not None:
            self.blocks.release(slot)
            if not self.blocks.ensure(slot, n):
                self.blocks.release(slot)
                raise RuntimeError(
                    f"KV pool exhausted admitting a {n}-token prompt "
                    f"(free blocks: {self.blocks.free_blocks} x "
                    f"{self.paged.block_size}); gate admissions on "
                    "can_admit()")
            row_table = jnp.asarray(self.blocks.table()[slot])
            C = self.paged.prefill_chunk
            toks = np.asarray(prompt[:-1], np.int32)
            for c0 in range(0, len(toks), C):
                chunk = toks[c0:c0 + C]
                count = len(chunk)
                padded = np.zeros(C, np.int32)
                padded[:count] = chunk
                with self.obs.span("prefill-chunk", track=self.obs_track,
                                   cat="lm", args={"slot": slot, "pos": c0,
                                                   "tokens": count}):
                    lg, self.pool = self._prefill_paged(
                        self.params, self.pool, row_table, jnp.int32(c0),
                        jnp.asarray(padded[None]), jnp.int32(count))
                self.prefill_dispatches += 1
                logits = lg
        else:
            with self.obs.span("prefill", track=self.obs_track, cat="lm",
                               args={"slot": slot, "tokens": n - 1}):
                self.cache = self._reset_slot(self.cache, self._fresh_cache,
                                              jnp.int32(slot))
                for t in range(n - 1):
                    lg, self.cache = self._prefill(
                        self.params, self.cache, prompt[t], jnp.int32(slot))
                    self.prefill_dispatches += 1
                    logits = lg[slot]
        if self.obs.enabled and self.prefill_dispatches > disp0:
            self.obs.count("prefill_dispatches",
                           self.prefill_dispatches - disp0,
                           engine=self.obs_track)
        self.active[slot] = True
        self.generated[slot] = [int(prompt[-1])]
        self.generated_logits[slot] = []
        self.lens[slot] = n - 1
        self.overflowed[slot] = False
        self.sampling[slot] = sampling
        return logits

    # -- decode ------------------------------------------------------------

    def _park_full(self) -> None:
        """Park active slots that have no KV room for this step's write."""
        if self.paged is None:
            full = self.active & (self.lens >= self.max_len)
            if full.any():
                self.active[full] = False
                self.overflowed[full] = True
            return
        # Pool-exhaustion parking: grow each slot's block list for one more
        # position, in ascending slot order (deterministic under replay);
        # a slot the pool cannot serve parks but KEEPS its blocks — the
        # caller retires it and release_slot() returns them.
        for s in range(self.slots):
            if self.active[s] and \
                    not self.blocks.ensure(s, int(self.lens[s]) + 1):
                self.active[s] = False
                self.overflowed[s] = True

    def step(self, sampler="greedy", temperature=1.0, key=None):
        """One decode step for the active slots; returns sampled tokens.

        Slots out of KV room are parked first (``active`` cleared,
        ``overflowed`` set).  Returns ``None`` when parking leaves nothing
        active.  ``sampler="categorical"`` requires an explicit ``key`` and
        a positive ``temperature`` (validated here — both used to die as
        opaque jax errors); per-slot :class:`SamplingSpec`s from
        ``add_request`` override these engine-level args.
        """
        if sampler != "greedy":
            if key is None:
                raise ValueError(
                    f"sampler={sampler!r} needs an explicit PRNG key "
                    "(key=jax.random.PRNGKey(...)); only the greedy "
                    "sampler is key-free")
            if not temperature > 0:
                raise ValueError(
                    f"temperature must be > 0, got {temperature} — "
                    "temperature=0 is greedy argmax; use sampler='greedy'")
        self._park_full()
        if not self.active.any():
            return None
        last = jnp.asarray([
            self.generated[s][-1] if self.generated[s] else 0
            for s in range(self.slots)], dtype=jnp.int32)[:, None]
        if self.paged is not None:
            logits, self.pool, top, top_logit, held = self._decode_paged(
                self.params, self.pool, jnp.asarray(self.blocks.table()),
                jnp.asarray(self.lens, jnp.int32), last,
                jnp.asarray(self.active))
        else:
            logits, self.cache, top, top_logit = self._decode(
                self.params, self.cache, last, jnp.asarray(self.active))
            held = 0
        self.last_logits = logits
        self.decode_dispatches += 1
        kv_bytes = self._kv_step_bytes()
        self.kv_bytes_touched += kv_bytes
        if self.obs.enabled:
            self.obs.count("decode_dispatches", 1, engine=self.obs_track)
            self.obs.count("kv_bytes_touched", kv_bytes,
                           engine=self.obs_track)
        self.lens[self.active] += 1
        nxt, nxt_logit, held = jax.device_get((top, top_logit, held))
        nxt, nxt_logit = np.array(nxt), np.array(nxt_logit)
        self.held_picks_total += int(held)
        sampled = sampler != "greedy"
        if sampled:
            nxt = np.array(jax.random.categorical(
                key, logits[:, -1] / temperature))
        for s in range(self.slots):
            if self.active[s] and self.sampling[s] is not None:
                nxt[s] = lm_sampling.sample_token(
                    logits[s, -1], self.sampling[s], int(self.lens[s]))
                sampled = True
        if sampled:  # the chosen tokens' logits, in one gather and pull
            nxt_logit = np.array(jnp.take_along_axis(
                logits[:, -1], jnp.asarray(nxt, jnp.int32)[:, None], -1)[:, 0])
        for s in range(self.slots):
            if not self.active[s]:
                continue
            self.generated[s].append(int(nxt[s]))
            self.generated_logits[s].append(float(nxt_logit[s]))
        return jnp.asarray(nxt)

    # -- warm handoff ------------------------------------------------------

    def resize(self, slots: int, carry=()) -> None:
        """Re-slot to ``slots`` rows, carrying ``carry`` old slots into new
        rows 0.. in order — a pure block-table edit: carried slots' KV
        blocks are untouched in the pool, so their decode trajectories are
        bit-equal across the resize (the ``Engine.resize`` warm-handoff
        contract).  Paged engines only; the contiguous cache would need a
        buffer reshape (``LMEngine.resize`` replays instead)."""
        if self.paged is None:
            raise ValueError(
                "resize() needs the paged KV path (paged=PagedConfig()); "
                "the contiguous cache cannot re-slot without a reshape")
        carry = list(carry)
        if any(c < 0 or c >= self.slots for c in carry):
            raise ValueError(f"carry={carry} outside 0..{self.slots - 1}")
        self.blocks.resize(slots, carry)
        self.active = np.array(
            [self.active[c] for c in carry] + [False] * (slots - len(carry)),
            bool)
        self.lens = np.array(
            [self.lens[c] for c in carry] + [0] * (slots - len(carry)),
            np.int64)
        self.overflowed = np.array(
            [self.overflowed[c] for c in carry]
            + [False] * (slots - len(carry)), bool)
        self.generated = [self.generated[c] for c in carry] + \
            [[] for _ in range(slots - len(carry))]
        self.generated_logits = [self.generated_logits[c] for c in carry] \
            + [[] for _ in range(slots - len(carry))]
        self.sampling = [self.sampling[c] for c in carry] + \
            [None] * (slots - len(carry))
        self.slots = slots


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Chrome-trace JSON of the run to PATH")
    args = ap.parse_args()
    # The demo-main keeps its console output, but through logging (library
    # code must never print): a plain-message handler on this module's
    # logger, only when the app hasn't configured one itself.
    if not logging.getLogger().handlers and not log.handlers:
        handler = logging.StreamHandler()
        handler.setFormatter(logging.Formatter("%(message)s"))
        log.addHandler(handler)
        log.setLevel(logging.INFO)
    enable_compile_cache()
    spec = ARCHS[args.arch]
    cfg = spec.smoke() if args.smoke else spec.full()
    key = jax.random.PRNGKey(0)
    params, _ = T.init(key, cfg)
    log.info("%s: %s params; serving batch=%d",
             cfg.name, format(T.param_count(params), ","), args.batch)
    rec = obs_mod.Recorder() if args.trace else None
    eng = ServeEngine(cfg, params, args.batch, args.prompt_len + args.gen + 1,
                      paged=PagedConfig() if args.paged else None, obs=rec)
    prompt = jax.random.randint(key, (args.prompt_len,), 0, cfg.vocab)
    t0 = time.perf_counter()
    for s in range(args.batch):
        eng.add_request(s, prompt)
    prefill_t = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(args.gen):
        eng.step()
    jax.block_until_ready(eng.pool if args.paged else eng.cache)
    dec_t = time.perf_counter() - t0
    tps = args.batch * args.gen / dec_t
    log.info("prefill %.1fms (%d dispatches); decode %d steps x %d slots "
             "in %.1fms -> %.1f tok/s", prefill_t * 1e3,
             eng.prefill_dispatches, args.gen, args.batch, dec_t * 1e3, tps)
    log.info("sample: %s", eng.generated[0][:16])
    if rec is not None:
        rec.write_chrome_trace(args.trace)
        log.info("trace written to %s (open in ui.perfetto.dev)", args.trace)


if __name__ == "__main__":
    main()
