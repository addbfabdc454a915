"""Contiguous-cache LM serving regressions: prefill slot isolation on the
layout itself, and greedy streams and KV-capacity parking through
LMEngine."""
import jax
import numpy as np
import pytest

import repro.runtime as rt
from repro.configs.registry import ARCHS
from repro.lm.kv import ContiguousKV
from repro.nn import transformer as T


def _model(arch="llama3.2-3b"):
    cfg = ARCHS[arch].smoke()
    params, _ = T.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(seed, n, cfg):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         cfg.vocab), np.int32)


def test_prefill_writes_only_target_slot():
    cfg, params = _model()
    kv = ContiguousKV(cfg, params, 3, 32)
    before = [np.asarray(leaf).copy() for leaf in jax.tree.leaves(kv.cache)]
    logits, dispatches = kv.prefill(0, _prompt(1, 5, cfg))
    assert logits.shape == (1, cfg.vocab) and bool(np.isfinite(logits).all())
    assert dispatches == 4  # prompt[:-1], one token a dispatch
    # every cache leaf is [periods, batch, ...]: rows 1.. must be untouched
    for old, new in zip(before, jax.tree.leaves(kv.cache)):
        np.testing.assert_array_equal(old[:, 1:], np.asarray(new)[:, 1:])


def test_prefill_matches_single_slot_reference():
    cfg, params = _model()
    kv = ContiguousKV(cfg, params, 3, 32)
    ref = ContiguousKV(cfg, params, 1, 32)
    prompt = _prompt(2, 6, cfg)
    # fill slot 1 first: slot 2's prefill must see a fresh row regardless
    kv.prefill(1, _prompt(3, 4, cfg))
    got, _ = kv.prefill(2, prompt)
    want, _ = ref.prefill(0, prompt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["llama3.2-3b", "minicpm-2b"])
def test_greedy_decode_isolated_per_slot(arch):
    """A request's greedy stream does not depend on what shares the batch."""
    cfg, params = _model(arch)
    eng = rt.LMEngine(cfg, params, slots=2, max_len=32, decode_per_step=2)
    ref = rt.LMEngine(cfg, params, slots=1, max_len=32, decode_per_step=2)
    p0, p1 = _prompt(4, 5, cfg), _prompt(5, 7, cfg)
    a = eng.submit(p0, max_new_tokens=6)
    eng.submit(p1, max_new_tokens=6)
    r = ref.submit(p0, max_new_tokens=6)
    got = {q.id: q.tokens for q in eng.drain()}
    want = {q.id: q.tokens for q in ref.drain()}
    assert len(got[a]) == 6 and all(0 <= t < cfg.vocab for t in got[a])
    assert got[a] == want[r]


def test_empty_prompt_returns_none():
    cfg, params = _model()
    eng = rt.LMEngine(cfg, params, slots=2, max_len=32)
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit(np.zeros((0,), np.int32))
    assert eng.in_flight == 0
    # one-token prompt: nothing to prefill, the token is fed by decode
    kv = ContiguousKV(cfg, params, 2, 32)
    assert kv.prefill(1, np.asarray([7], np.int32)) == (None, 0)


def test_decode_parks_slot_at_kv_capacity():
    """Decoding past max_len must park the slot, not silently clamp the KV
    write onto the last cache position."""
    cfg, params = _model()
    eng = rt.LMEngine(cfg, params, slots=2, max_len=8, decode_per_step=1)
    rid = eng.submit(_prompt(7, 5, cfg), max_new_tokens=10)
    for _ in range(4):  # len 4 -> 8: exactly the remaining capacity
        assert eng.step() == []
    assert eng.active[0] and eng.lens[0] == 8
    dispatches = eng.decode_dispatches
    done = eng.step()  # full slot parked; nothing left to decode
    assert eng.decode_dispatches == dispatches  # KV untouched
    assert [q.id for q in done] == [rid]
    assert done[0].truncated and len(done[0].tokens) == 4  # none past it
    assert eng.lens[0] == 8 and not eng.active[0]
    # the parked slot is reusable: a fresh request resets its state
    eng.submit(np.asarray([3, 1], np.int32), max_new_tokens=1)
    eng._fill()
    assert eng.active[0] and eng.lens[0] == 1


def test_capacity_parking_leaves_other_slots_running():
    cfg, params = _model()
    eng = rt.LMEngine(cfg, params, slots=2, max_len=8, decode_per_step=1)
    a = eng.submit(_prompt(8, 7, cfg), max_new_tokens=10)
    eng.submit(_prompt(9, 2, cfg), max_new_tokens=10)
    done = []
    for _ in range(5):
        done += eng.step()
    assert [q.id for q in done] == [a] and done[0].truncated  # hit capacity
    assert eng.active[1] and eng.lens[1] == 6  # slot 1 keeps decoding


def test_overlong_prompt_rejected():
    cfg, params = _model()
    eng = rt.LMEngine(cfg, params, slots=1, max_len=8)
    with pytest.raises(ValueError, match="exceeds the engine's KV capacity"):
        eng.submit(np.zeros((9,), np.int32))
    assert eng.in_flight == 0 and not eng.active[0]  # rejected at submit


def test_last_prompt_token_kv_written_once():
    """The last prompt token must enter the KV cache via decode, not
    twice."""
    cfg, params = _model()
    kv = ContiguousKV(cfg, params, 1, 32)
    prompt = _prompt(6, 5, cfg)
    kv.prefill(0, prompt)

    def lens():  # the per-row "len" counters
        return [np.asarray(leaf) for leaf in jax.tree.leaves(kv.cache)
                if np.asarray(leaf).ndim == 2]

    assert all((n[:, 0] == 4).all() for n in lens())  # prompt[:-1] only
    kv.decode(prompt[-1:][None], np.asarray([4]), np.ones(1, bool))
    assert all((n[:, 0] == 5).all() for n in lens())  # prompt[-1] landed once
