"""Paged-KV serving: block-table pool semantics, paged-vs-contiguous token
stream equality, chunked prefill, pool-exhaustion parking, LMEngine resize
warm handoff, and the sampling spec path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.runtime as rt
from repro.configs.registry import ARCHS
from repro.launch.programs import primitive_names
from repro.lm import model as lm_model
from repro.lm.kv import ContiguousKV, PagedKV
from repro.lm.paging import BlockTablePool, PagedConfig
from repro.lm.sampling import SamplingSpec
from repro.nn import transformer as T


@pytest.fixture(scope="module")
def smoke():
    cfg = ARCHS["llama3.2-3b"].smoke()
    params, _ = T.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(seed, n, cfg):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         cfg.vocab), np.int32)


# -- PagedConfig / BlockTablePool unit ---------------------------------------

def test_paged_config_validation():
    with pytest.raises(ValueError, match="block_size"):
        PagedConfig(block_size=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        PagedConfig(prefill_chunk=0)
    with pytest.raises(ValueError, match="num_blocks"):
        PagedConfig(num_blocks=0)
    with pytest.raises(TypeError, match="PagedConfig"):
        rt.LMEngine(None, None, slots=1, max_len=8, paged=True)


def test_pool_alloc_release_and_table():
    pool = BlockTablePool(num_blocks=4, block_size=4, slots=2, table_width=3)
    assert pool.trash == 4 and pool.free_blocks == 4
    assert pool.ensure(0, 5)  # 2 blocks
    assert pool.ensure(1, 4)  # 1 block
    t = pool.table()
    assert t.shape == (2, 3)
    assert list(t[0]) == [0, 1, 4]  # deterministic ids, trash-padded
    assert list(t[1]) == [2, 4, 4]
    assert not pool.ensure(1, 13)  # table width (3 blocks = 12) exceeded
    assert pool.ensure(1, 8) and not pool.ensure(0, 12)  # pool drained
    assert pool.release(0) == 2 and pool.free_blocks == 2
    assert pool.ensure(1, 12)  # released blocks are reusable
    assert pool.capacity(1) == 12


def test_pool_resize_carries_block_lists():
    pool = BlockTablePool(num_blocks=6, block_size=4, slots=3, table_width=2)
    for s in range(3):
        pool.ensure(s, 8)
    assert pool.free_blocks == 0
    rows1 = list(pool.rows[1])
    pool.resize(2, carry=[1])  # slots 0 and 2 freed, old slot 1 -> row 0
    assert pool.slots == 2 and pool.rows[0] == rows1 and pool.rows[1] == []
    assert pool.free_blocks == 4
    with pytest.raises(ValueError, match="cannot carry"):
        pool.resize(1, carry=[0, 1])


# -- paged vs contiguous serving ---------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_stream_equals_contiguous_greedy(smoke, kv_dtype):
    cfg, params = smoke
    if kv_dtype == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
        params, _ = T.init(jax.random.PRNGKey(0), cfg)
    paged = PagedConfig(block_size=8, prefill_chunk=4)
    ref = ContiguousKV(cfg, params, 3, 32)
    kv = PagedKV(cfg, params, 3, 32, paged)
    # mixed lengths: 1-token (nothing to prefill), off/at chunk boundary
    prompts = [_prompt(s + 1, n, cfg) for s, n in enumerate((1, 5, 9))]
    for s, p in enumerate(prompts):
        lr, _ = ref.prefill(s, p)
        lp, _ = kv.prefill(s, p)
        if lr is None:
            assert lp is None
        else:  # chunked prefill emits the SAME last-token logits, bit-equal
            np.testing.assert_array_equal(np.asarray(lr), np.asarray(lp))
    streams = []
    for layout in (None, paged):
        eng = rt.LMEngine(cfg, params, slots=3, max_len=32,
                          decode_per_step=2, paged=layout)
        ids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        done = {r.id: r.tokens for r in eng.drain()}
        streams.append([done[i] for i in ids])
    assert streams[0] == streams[1]
    assert all(len(t) == 6 for t in streams[0])


def test_greedy_stream_bitstable_across_block_sizes(smoke):
    cfg, params = smoke
    streams = []
    for bs, chunk in ((4, 3), (8, 4), (16, 8)):
        eng = rt.LMEngine(cfg, params, slots=2, max_len=32,
                          decode_per_step=2,
                          paged=PagedConfig(block_size=bs,
                                            prefill_chunk=chunk))
        eng.submit(_prompt(2, 6, cfg), max_new_tokens=6)
        eng.submit(_prompt(3, 9, cfg), max_new_tokens=6)
        streams.append([r.tokens for r in eng.drain()])
    assert streams[0] == streams[1] == streams[2]


def test_chunked_prefill_dispatch_count(smoke):
    cfg, params = smoke
    eng = rt.LMEngine(cfg, params, slots=2, max_len=32,
                      paged=PagedConfig(block_size=8, prefill_chunk=4))
    eng.submit(_prompt(4, 10, cfg))  # 9 prefill tokens -> 3 chunks
    eng._fill()
    assert eng.prefill_dispatches == 3
    eng.submit(_prompt(5, 5, cfg))   # 4 prefill tokens -> 1 chunk
    eng._fill()
    assert eng.prefill_dispatches == 4
    ref = rt.LMEngine(cfg, params, slots=2, max_len=32)
    ref.submit(_prompt(4, 10, cfg))
    ref._fill()
    assert ref.prefill_dispatches == 9  # contiguous: one per token


def test_one_pallas_call_per_decode_step(smoke):
    """The flash path runs EXACTLY one pallas_call per decode dispatch —
    the kernel sits inside the scan-over-periods body."""
    cfg, params = smoke
    eng = PagedKV(cfg, params, 2, 32, PagedConfig(block_size=8))

    jaxpr = jax.make_jaxpr(
        lambda p, pool, table, lens, tok, act: lm_model.decode_step_paged(
            p, cfg, pool, table, lens, tok, act, use_flash=True,
            interpret=True))(
        params, eng.pool, jnp.asarray(eng.blocks.table()),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 1), jnp.int32),
        jnp.ones((2,), bool))
    names = primitive_names(jaxpr)
    assert names.count("pallas_call") == 1, names.count("pallas_call")


# -- capacity: pool-limited, not max_len-limited -----------------------------

def test_pool_exhaustion_parks_and_recovers(smoke):
    cfg, params = smoke
    kv = PagedKV(cfg, params, 2, 32,
                 PagedConfig(block_size=4, num_blocks=3,
                             max_blocks_per_slot=3))
    kv.prefill(0, _prompt(6, 4, cfg))  # 1 block
    kv.prefill(1, _prompt(7, 5, cfg))  # 2 blocks -> pool drained
    assert kv.blocks.free_blocks == 0
    # slot 0 cannot take a 2nd block (len 4 -> 5); slot 1 runs on in its 2
    assert not kv.grow(0, 5)
    assert kv.grow(1, 8) and not kv.grow(1, 9)
    lens, act = np.asarray([4, 4]), np.asarray([False, True])
    logits, *_ = kv.decode(np.zeros((2, 1), np.int32), lens, act)
    assert bool(np.isfinite(np.asarray(logits[1])).all())
    # releasing the parked slot lets slot 1 grow into the freed block
    kv.release(0)
    assert kv.blocks.free_blocks == 1
    assert kv.grow(1, 11)  # len 8 -> 9 crosses into a 3rd block
    assert kv.blocks.free_blocks == 0 and kv.blocks.capacity(1) == 12


def test_slot_capacity_exceeds_max_len_when_pool_allows(smoke):
    cfg, params = smoke
    paged = PagedConfig(block_size=8, num_blocks=4, max_blocks_per_slot=4)
    assert PagedKV(cfg, params, 1, 8, paged).capacity == 32  # not max_len=8
    eng = rt.LMEngine(cfg, params, slots=1, max_len=8, decode_per_step=1,
                      paged=paged)
    rid = eng.submit(_prompt(8, 12, cfg), max_new_tokens=8)  # > max_len
    for _ in range(4):
        assert eng.step() == []
    assert eng.lens[0] == 15 and eng.active[0]
    done = eng.drain()
    assert [r.id for r in done] == [rid] and not done[0].truncated
    with pytest.raises(ValueError, match="exceeds the engine's KV capacity"):
        eng.submit(_prompt(8, 33, cfg))


def test_lm_engine_defers_admission_until_pool_frees(smoke):
    cfg, params = smoke
    eng = rt.LMEngine(cfg, params, slots=2, max_len=32, decode_per_step=2,
                      paged=PagedConfig(block_size=4, num_blocks=3,
                                        max_blocks_per_slot=3))
    a = eng.submit(_prompt(9, 8, cfg), max_new_tokens=6)   # 2 blocks
    b = eng.submit(_prompt(10, 8, cfg), max_new_tokens=6)  # must wait
    eng.step()
    assert eng._owner[0] is not None and eng._owner[0].id == a
    assert eng._owner[1] is None and len(eng._queue) == 1  # b deferred
    done = {r.id for r in eng.drain()}
    assert done == {a, b}  # b admitted once a's blocks came back


# -- LMEngine.resize warm handoff --------------------------------------------

def _submit_all(eng, cfg, lens=(4, 5, 6), mnt=8):
    return [eng.submit(_prompt(20 + i, n, cfg), max_new_tokens=mnt)
            for i, n in enumerate(lens)]


def test_paged_resize_shrink_carries_bit_equal(smoke):
    cfg, params = smoke
    kw = dict(slots=3, max_len=32, decode_per_step=2,
              paged=PagedConfig(block_size=8, prefill_chunk=4))
    eng = rt.LMEngine(cfg, params, **kw)
    ref = rt.LMEngine(cfg, params, **kw)
    _submit_all(eng, cfg)
    _submit_all(ref, cfg)
    eng.step()
    ref.step()  # all three slots mid-flight
    eng.resize(2)  # slot 2's request replays; 0/1 carry verbatim
    assert eng.resizes_total == 1 and eng.slots == 2
    got = {r.id: r.tokens for r in eng.drain()}
    want = {r.id: r.tokens for r in ref.drain()}
    assert got == want


def test_paged_resize_grow_carries_bit_equal(smoke):
    cfg, params = smoke
    kw = dict(slots=2, max_len=32, decode_per_step=2,
              paged=PagedConfig(block_size=8, prefill_chunk=4))
    eng = rt.LMEngine(cfg, params, **kw)
    ref = rt.LMEngine(cfg, params, **kw)
    _submit_all(eng, cfg)
    _submit_all(ref, cfg)
    eng.step()
    ref.step()
    eng.resize(3)  # queued third request gets a slot next step
    got = {r.id: r.tokens for r in eng.drain()}
    want = {r.id: r.tokens for r in ref.drain()}
    assert got == want


def test_contiguous_resize_replays_bit_equal(smoke):
    cfg, params = smoke
    eng = rt.LMEngine(cfg, params, slots=3, max_len=32, decode_per_step=2)
    ref = rt.LMEngine(cfg, params, slots=3, max_len=32, decode_per_step=2)
    _submit_all(eng, cfg)
    _submit_all(ref, cfg)
    eng.step()
    ref.step()
    eng.resize(2)  # contiguous cannot carry: every live request replays
    assert eng.resizes_total == 1
    got = {r.id: r.tokens for r in eng.drain()}
    want = {r.id: r.tokens for r in ref.drain()}
    assert got == want
    # the counters outlive the rebuilt cache: the replays prefill again
    assert eng.prefill_dispatches > ref.prefill_dispatches


def test_resize_preserves_sampled_requests(smoke):
    """A displaced sampled request replays bit-equal: its keys derive from
    (seed, position), not from engine state."""
    cfg, params = smoke
    spec = SamplingSpec(temperature=0.7, top_k=32, seed=11)
    kw = dict(slots=2, max_len=32, decode_per_step=2,
              paged=PagedConfig(block_size=8))
    eng = rt.LMEngine(cfg, params, **kw)
    ref = rt.LMEngine(cfg, params, **kw)
    for e in (eng, ref):
        e.submit(_prompt(30, 5, cfg), max_new_tokens=6, sampling=spec)
        e.submit(_prompt(31, 4, cfg), max_new_tokens=6, sampling=spec)
    eng.step()
    ref.step()
    eng.resize(1)  # slot 1's sampled request is displaced and replays
    got = {r.id: r.tokens for r in eng.drain()}
    want = {r.id: r.tokens for r in ref.drain()}
    assert got == want


# -- sampling specs and step() validation ------------------------------------

def test_sampling_spec_validation():
    with pytest.raises(ValueError, match="temperature"):
        SamplingSpec(temperature=0.0)
    with pytest.raises(ValueError, match="top_k"):
        SamplingSpec(top_k=0)


def test_step_sampler_footguns_die_loudly(smoke):
    cfg, params = smoke
    eng = rt.LMEngine(cfg, params, slots=1, max_len=16)
    with pytest.raises(TypeError, match="SamplingSpec"):
        eng.submit(_prompt(40, 4, cfg), sampling={"temperature": 1.0})
    with pytest.raises(TypeError, match="SamplingSpec"):
        eng.submit(_prompt(40, 4, cfg), sampling=0.7)
    assert eng.in_flight == 0  # rejected at submit, before any slot


def test_sampled_stream_deterministic_across_engines(smoke):
    """Same request + seed -> same tokens, regardless of slot count, paging
    or burst size (the key depends only on (seed, position))."""
    cfg, params = smoke
    spec = SamplingSpec(temperature=0.8, top_k=16, seed=42)
    p = _prompt(41, 4, cfg)
    outs = []
    for kw in (dict(slots=2, decode_per_step=2,
                    paged=PagedConfig(block_size=8)),
               dict(slots=1, decode_per_step=3),
               dict(slots=3, decode_per_step=1,
                    paged=PagedConfig(block_size=4))):
        eng = rt.LMEngine(cfg, params, max_len=32, **kw)
        rid = eng.submit(p, max_new_tokens=6, sampling=spec)
        outs.append({r.id: r.tokens for r in eng.drain()}[rid])
    assert outs[0] == outs[1] == outs[2]
    assert len(outs[0]) == 6


# -- misc --------------------------------------------------------------------

def test_paging_rejects_unsupported_stacks():
    cfg = dataclasses.replace(ARCHS["llama3.2-3b"].smoke(),
                              block_pattern=("mamba_mlp",))
    with pytest.raises(ValueError, match="attention-only"):
        lm_model.check_paging_supported(cfg)


def test_kv_bytes_metric_scales_with_live_blocks(smoke):
    cfg, params = smoke
    eng = rt.LMEngine(cfg, params, slots=2, max_len=64, decode_per_step=1,
                      paged=PagedConfig(block_size=8))
    ref = rt.LMEngine(cfg, params, slots=2, max_len=64, decode_per_step=1)
    for e in (eng, ref):
        e.submit(_prompt(43, 5, cfg), max_new_tokens=4)
        e.step()
    # paged reads ceil(len/bs) blocks; contiguous reads slots * max_len
    assert 0 < eng.kv_bytes_touched < ref.kv_bytes_touched
