"""The two KV layouts an :class:`repro.runtime.LMEngine` serves from.

Each holds only the device side of LM serving: the weights (``params``,
read by its programs at call time), its cache or pool, its jitted programs
and the layout's capacity rules.  Every request and slot, and all host
bookkeeping, live in ``LMEngine``, which picks one layout at construction:

  * :class:`ContiguousKV`: one ``[periods, slots, max_len, ...]`` cache,
    one prefill dispatch per prompt token; the layout for the block kinds
    the pool does not cover (mamba / xLSTM state, cross-attention,
    encoders, M-RoPE, vision prefixes);
  * :class:`PagedKV`: a shared block pool + per-slot block tables
    (:mod:`repro.lm.paging`), one dispatch per ``prefill_chunk`` prompt
    tokens, flash-decode attention, capacity limited by the pool instead
    of ``max_len``, and ``resize`` as a block-table edit.  Latent-attention
    (MLA) stacks serve here only.

Both share one interface: ``capacity``, ``can_admit``, ``claims``,
``grow``, ``prefill``, ``decode``, ``release``, ``kv_step_bytes``,
``resize`` and ``paged`` (whether ``resize`` carries rows).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.lm import model as lm_model
from repro.lm.paging import BlockTablePool, PagedConfig, cdiv
from repro.nn import transformer as T


def _greedy(logits):
    """(argmax token [B] int32, its logit [B] f32) of the last position."""
    last = logits[:, -1]
    return jnp.argmax(last, axis=-1).astype(jnp.int32), jnp.max(last, axis=-1)


def kv_token_bytes(cfg) -> int:
    """Modeled KV bytes one cached position holds across every attention
    layer."""
    G = cfg.n_kv_heads
    dh = cfg.head_dim if cfg.head_dim is not None else \
        cfg.d_model // cfg.n_heads
    int8 = cfg.kv_cache_dtype == "int8"
    per_tok = 2 * G * dh * (1 if int8 else 2) + (2 * G * 4 if int8 else 0)
    if cfg.mla is not None:  # one bf16 latent entry serves every head
        per_tok = 2 * cfg.mla.latent_dim
    n_attn = sum(k.startswith("attn") for k in cfg.block_pattern) \
        * cfg.n_periods + cfg.first_dense
    return per_tok * n_attn


class ContiguousKV:
    """One cache row of ``max_len`` positions per slot."""

    paged = False  # resize re-slots nothing: the engine replays live rows
    kv_block = None

    def __init__(self, cfg, params, slots: int, max_len: int):
        self.cfg, self.params, self.max_len = cfg, params, max_len
        self.capacity = max_len
        self._token_bytes = kv_token_bytes(cfg)
        self.resize(slots)

        # The active-slot select is fused into the program: inactive slots
        # keep their old cache rows (their dummy token must not advance the
        # KV length a later prefill writes against).  Every cache leaf is
        # [periods, batch, ...] (see T.init_cache).
        def decode_masked(p, c, tok, act):
            logits, new = T.decode_step(p, cfg, c, tok)
            merged = jax.tree.map(
                lambda o, n: jnp.where(
                    act.reshape((1,) + act.shape + (1,) * (o.ndim - 2)), n, o),
                c, new)
            return logits, merged

        def decode_greedy(p, c, tok, act):
            logits, merged = decode_masked(p, c, tok, act)
            return (logits, merged, *_greedy(logits))

        # prefill one token into ONE slot: decode the whole (static-shape)
        # batch but write back only the target slot's row
        def prefill(p, c, tok, slot):
            rows = jax.tree.leaves(c)[0].shape[1]
            return decode_masked(
                p, c, jnp.broadcast_to(tok, (rows, 1)).astype(jnp.int32),
                jnp.arange(rows) == slot)

        self._decode = jax.jit(decode_greedy)
        self._prefill = jax.jit(prefill)
        # slot reset as ONE dispatch with the stale cache donated: only the
        # target row of each leaf is rewritten in place
        self._reset_slot = jax.jit(
            lambda c, f, slot: jax.tree.map(
                lambda cl, fl: cl.at[:, slot].set(jnp.take(fl, slot, axis=1)),
                c, f),
            donate_argnums=(0,))

    def can_admit(self, tokens: int, reserved: int) -> bool:
        return True  # every slot owns a whole row

    def claims(self, live, tokens: int, reach: int) -> int:
        return 0

    def grow(self, slot: int, tokens: int) -> bool:
        return tokens <= self.max_len

    def prefill(self, slot: int, prompt, obs=obs_mod.NULL, track="lm"):
        """Reset row ``slot`` and write ``prompt[:-1]`` into it; the last
        token is fed by the next decode step, so its KV is written once.
        Returns (logits [1, V] after the last prefilled token or None,
        dispatches)."""
        n = len(prompt) - 1
        lg = None
        with obs.span("prefill", track=track, cat="lm",
                      args={"slot": slot, "tokens": n}):
            # from a pristine cache: xLSTM stabilizer rows start at -1e9
            self.cache = self._reset_slot(self.cache, self._fresh_cache,
                                          jnp.int32(slot))
            for t in range(n):
                lg, self.cache = self._prefill(self.params, self.cache,
                                               prompt[t], jnp.int32(slot))
        return (None if lg is None else lg[slot]), n

    def decode(self, last, lens, active):
        """One masked decode step: (logits [B, 1, V], greedy token [B], its
        logit [B], held expert picks)."""
        logits, self.cache, top, top_logit = self._decode(
            self.params, self.cache, jnp.asarray(last), jnp.asarray(active))
        return logits, top, top_logit, 0

    def release(self, slot: int) -> None:
        pass  # prefill resets the row

    def kv_step_bytes(self, lens) -> int:
        """A decode step reads the whole cache."""
        return len(lens) * self.max_len * self._token_bytes

    def resize(self, slots: int, carry=()) -> None:
        """Fresh rows for ``slots`` slots: the cache cannot re-slot without
        a reshape, so nothing is carried."""
        if carry:
            raise ValueError("the contiguous cache carries no slots")
        self.cache = T.init_cache(self.cfg, slots, self.max_len)
        self._fresh_cache = T.init_cache(self.cfg, slots, self.max_len)


class PagedKV:
    """Block-table pool: slots grow block by block out of one free list."""

    paged = True  # resize carries rows: a block-table edit

    def __init__(self, cfg, params, slots: int, max_len: int,
                 paged: PagedConfig):
        self.cfg, self.params, self.config = cfg, params, paged
        self.kv_block = paged.block_size
        nb = paged.resolve_num_blocks(slots, max_len)
        self.blocks = BlockTablePool(
            nb, paged.block_size, slots,
            paged.resolve_table_width(slots, max_len))
        self.capacity = self.blocks.slot_capacity
        self.pool = lm_model.init_pool(cfg, nb, paged.block_size)
        self._token_bytes = kv_token_bytes(cfg)

        # The pool is donated through every dispatch (it is THE mutable
        # serving state); closures carry no batch dim, so resize is pure
        # host-side re-slotting + an automatic shape recompile.  The greedy
        # pick and its logit ride in the decode program, so one pull per
        # step brings tokens, logits and held-expert picks back.
        def decode(p, pool, table, lens, tok, act):
            logits, pool, held = lm_model.decode_step_paged(
                p, cfg, pool, table, lens, tok, act,
                interpret=paged.interpret)
            return (logits, pool, *_greedy(logits),
                    jnp.sum(jnp.where(act, held, 0)))

        self._decode = jax.jit(decode, donate_argnums=(1,))
        self._prefill = jax.jit(
            lambda p, pool, row_table, len0, tok, count:
            lm_model.prefill_chunk_paged(p, cfg, pool, row_table, len0,
                                         tok, count),
            donate_argnums=(1,))

    def _blocks(self, tokens: int) -> int:
        """Blocks ``tokens`` positions take, capped by what a slot and the
        pool can ever hold."""
        return min(cdiv(min(tokens, self.capacity), self.kv_block),
                   self.blocks.num_blocks)

    def can_admit(self, tokens: int, reserved: int) -> bool:
        """Whether a ``tokens``-token prompt's blocks are free beyond
        ``reserved`` of them."""
        return self.blocks.free_blocks - reserved >= \
            cdiv(tokens, self.kv_block)

    def claims(self, live, tokens: int, reach: int) -> int:
        """Blocks the live slots (``(slot, tokens it may reach)`` pairs) may
        still take, plus what a ``tokens``-token prompt that may reach
        ``reach`` needs beyond its prompt: admission leaves them free, so a
        pool that admits never parks a slot mid-generation."""
        rows = self.blocks.rows
        held = sum(max(0, self._blocks(t) - len(rows[s])) for s, t in live)
        return held + max(0, self._blocks(reach)
                          - cdiv(tokens, self.kv_block))

    def grow(self, slot: int, tokens: int) -> bool:
        """Grow the slot's block list to ``tokens`` positions; False when
        the pool or the slot's table width is exhausted (the slot keeps
        its blocks until released)."""
        return self.blocks.ensure(slot, tokens)

    def prefill(self, slot: int, prompt, obs=obs_mod.NULL, track="lm"):
        """Take the blocks of the whole prompt for ``slot`` and write
        ``prompt[:-1]`` in ``prefill_chunk``-token dispatches; the last token
        is fed by the next decode step, so its KV is written once.  Returns
        (logits [1, V] after the last prefilled token or None,
        dispatches)."""
        n = len(prompt)
        self.blocks.release(slot)
        if not self.blocks.ensure(slot, n):
            self.blocks.release(slot)
            raise RuntimeError(
                f"KV pool exhausted admitting a {n}-token prompt (free "
                f"blocks: {self.blocks.free_blocks} x {self.kv_block}); "
                "gate admissions on can_admit()")
        row_table = jnp.asarray(self.blocks.table()[slot])
        C = self.config.prefill_chunk
        toks = np.asarray(prompt[:-1], np.int32)
        logits = None
        for c0 in range(0, len(toks), C):
            chunk = toks[c0:c0 + C]
            padded = np.zeros(C, np.int32)
            padded[:len(chunk)] = chunk
            with obs.span("prefill-chunk", track=track, cat="lm",
                          args={"slot": slot, "pos": c0,
                                "tokens": len(chunk)}):
                logits, self.pool = self._prefill(
                    self.params, self.pool, row_table, jnp.int32(c0),
                    jnp.asarray(padded[None]), jnp.int32(len(chunk)))
        return logits, cdiv(len(toks), C)

    def decode(self, last, lens, active):
        """One masked decode step: (logits [B, 1, V], greedy token [B], its
        logit [B], held expert picks of the active rows)."""
        logits, self.pool, top, top_logit, held = self._decode(
            self.params, self.pool, jnp.asarray(self.blocks.table()),
            jnp.asarray(lens, jnp.int32), jnp.asarray(last),
            jnp.asarray(active))
        return logits, top, top_logit, held

    def release(self, slot: int) -> None:
        self.blocks.release(slot)

    def kv_step_bytes(self, lens) -> int:
        """A decode step gathers ``ceil((len + 1) / block)`` blocks a row."""
        bs = self.kv_block
        return sum(cdiv(int(n) + 1, bs) * bs for n in lens) \
            * self._token_bytes

    def resize(self, slots: int, carry=()) -> None:
        """Re-slot to ``slots`` rows, carrying old slots ``carry`` into rows
        0.. in order: their blocks are untouched in the pool, so their
        decode trajectories are bit-equal across the resize."""
        self.blocks.resize(slots, carry)
