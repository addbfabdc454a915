"""DeepSeek-V2-Lite's mechanisms at smoke size on the CPU: latent attention
(MLA) served from the paged latent pool, the latent flash-decode kernel,
YaRN, and the drop-free expert share with shared experts, each against the
plain f32 reference (:mod:`repro.models.deepseek_v2_ref`)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import deepseek_v2_lite as D
from repro.kernels.flash_decode import kernel as fdk
from repro.kernels.flash_decode import ref as fdr
from repro.lm.kv import ContiguousKV
from repro.lm.paging import PagedConfig
from repro.models import deepseek_v2_ref as R
from repro.nn import layers as L
from repro.nn import moe as Moe
from repro.nn import transformer as T
from repro.runtime import LMEngine, Runtime

PAGED = PagedConfig(block_size=8, prefill_chunk=4)


def _served(cfg, prompts, gen=5):
    """Serve ``prompts`` through Runtime -> LMEngine -> the paged pool
    (chunked prefill, then absorbed decode); returns (params, requests)."""
    params, _ = T.init(jax.random.PRNGKey(0), cfg)
    rt = Runtime()
    rt.register("lm", LMEngine(cfg, params, slots=3, max_len=48, paged=PAGED))
    rt.start()
    try:
        gids = [rt.submit("lm", p, max_new_tokens=gen) for p in prompts]
        reqs = [rt.result(g, timeout=300) for g in gids]
    finally:
        rt.stop()
    return params, reqs


def _prompts(vocab, lengths=(5, 13, 20)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


@pytest.mark.parametrize("activ", ["f32", "bf16"])
def test_served_logits_match_reference(activ):
    """(a) Paged prefill (chunks of 4 over blocks of 8, so chunks cross
    blocks) then absorbed decode, served through Runtime, against the
    reference's full forward teacher-forced on the served tokens.

    f32 activations: the reference rounds its latent to bf16 as the pool
    does, so the two differ by f32 summation order alone, plus a bf16
    rounding of the latent that a last-bit difference can tip; 1e-2 of the
    logit spread covers that and nothing more.  bf16 activations (as
    served on the chip): the program rounds every activation to bf16
    against an f32 reference, ~3-6% of the spread at this size; 0.15
    leaves room for that and still fails a dropped term (a layer's
    attention or expert output moves the logits by a large share of their
    spread)."""
    cfg = D.smoke()
    if activ == "f32":
        cfg = dataclasses.replace(cfg, activ_dtype=jnp.float32)
    params, reqs = _served(cfg, _prompts(cfg.vocab))
    tol, latent = (1e-2, jnp.bfloat16) if activ == "f32" else (0.15, None)
    for r in reqs:
        assert not r.result["truncated"] and len(r.result["tokens"]) == 5
        toks = np.asarray(r.result["tokens"])
        P = len(r.prompt)
        seq = np.zeros(32, np.int32)  # one length: causal, so padding after
        seq[:P + len(toks)] = np.concatenate([r.prompt, toks])  # is inert
        ref = np.asarray(R.forward_jit(params, cfg, jnp.asarray(seq),
                                       jnp.arange(P - 1, P - 1 + len(toks)),
                                       latent_dtype=latent, q_block=32))
        at = ref[np.arange(len(toks)), toks]
        err = np.abs(np.asarray(r.result["logits"]) - at) / ref.std(-1)
        assert err.max() < tol, (activ, err)


def _latent_setup(cfg, B=3, seed=0):
    m = cfg.mla
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    p, _ = L.init_mla(ks[0], m)
    bs, W = 8, 3
    pool = {"lat": jax.random.normal(
        ks[1], (B * W + 1, bs, m.pool_width)).astype(jnp.bfloat16)}
    table = jnp.arange(B * W, dtype=jnp.int32).reshape(B, W)
    x = jax.random.normal(ks[2], (B, 1, cfg.d_model), jnp.float32)
    return m, p, pool, table, x


def test_absorbed_decode_equals_non_absorbed():
    """(b) Decode folds W_UK into the query and W_UV into the output; the
    same cache attended non-absorbed (per-head keys and values
    up-projected from the latents) gives the same output, to f32
    reassociation."""
    cfg = D.smoke()
    m, p, pool, table, x = _latent_setup(cfg)
    lens = jnp.array([1, 9, 23], jnp.int32)  # pre-write lengths
    out, new = jax.jit(lambda pool: L.mla_decode_paged(
        p, x, pool, m, table, lens, jnp.ones(3, bool), use_flash=False))(pool)

    @jax.jit
    def non_absorbed(lat):  # [B, W*bs, latent_dim] -> [B, 1, d]
        q_nope, q_pe = L.mla_query(p, x, m, lens[:, None])
        k, v = L.mla_expand(p, lat.astype(jnp.float32), m)
        q = jnp.concatenate([q_nope, q_pe], -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * L.mla_softmax_scale(m)
        valid = jnp.arange(lat.shape[1])[None, :] <= lens[:, None]
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return L.dense(p["o"], o.reshape(3, 1, -1))

    want = non_absorbed(new["lat"][table].reshape(3, -1, m.pool_width)[
        ..., :m.latent_dim])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("layered", [False, True])
def test_latent_kernel_matches_ref(layered):
    """(c) The latent flash-decode kernel (interpret mode) against its
    ref.py over ragged lengths: one position, a short block, exactly one
    block, a tail block, the full table; with and without the stacked
    layer axis."""
    B, H, Dk, dv, bs, W = 5, 4, 48, 32, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(ks[0], (B, H, Dk))
    pool = jax.random.normal(ks[1], (2, B * W + 1, bs, Dk)).astype(
        jnp.bfloat16)
    table = jnp.arange(B * W, dtype=jnp.int32).reshape(B, W)[::-1]
    lens = jnp.array([1, 5, 8, 13, 24], jnp.int32)
    got = fdk.flash_decode(q, pool if layered else pool[1], None, table, lens,
                           v_width=dv, layer=jnp.int32(1) if layered else None,
                           interpret=True)
    want = fdr.flash_decode_ref(q, pool[1], None, table, lens, v_width=dv)
    assert got.shape == (B, H, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """(d) Over the 4 shares of 2 held experts each, the routed parts plus
    the shared expert counted once equal the uncut reference layer."""
    cfg = D.smoke(ep=1)
    mc = cfg.moe
    p, _ = Moe.init_moe(jax.random.PRNGKey(4), mc)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 7, cfg.d_model))
    shared = np.asarray(L.swiglu(p["shared"], x))
    total = shared.copy()
    for share in range(4):
        sc = D.smoke(ep=4, share=share).moe
        sl = slice(sc.held_from, sc.held_from + sc.held)
        ps = {**p, **{k: p[k][sl] for k in ("gate", "up", "down")}}
        y, _ = jax.jit(Moe.moe_share, static_argnums=2)(ps, x, sc)
        total += np.asarray(y) - shared
    want = np.asarray(jax.jit(R.moe, static_argnums=2)(
        p, x.reshape(-1, cfg.d_model), mc)).reshape(x.shape)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_skewed_routing_drops_no_token():
    """(e) Every token picks the same two held experts: the share computes
    each pick (the capacity path would keep ~cf * k / E of them)."""
    mc = dataclasses.replace(D.smoke().moe, n_shared=0)
    p, _ = Moe.init_moe(jax.random.PRNGKey(6), mc)
    bias = jnp.zeros((mc.d_model, mc.num_experts)).at[:, :2].set(5.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (2, 16, mc.d_model)))
    p = {**p, "router": p["router"] * 0 + bias}
    y, aux = jax.jit(Moe.moe_share, static_argnums=2)(p, x, mc)
    assert int(aux["held_picks"].sum()) == 2 * 16 * 2

    @jax.jit
    def dense(p, x):  # every token through each of its picks, no routing
        _, w, e = Moe.route(p, x, mc)
        out = jnp.stack([L.swiglu(
            {n: {"w": p[n][j]} for n in ("gate", "up", "down")}, x)
            for j in range(2)])  # [2, B, S, d]
        b, t = jnp.arange(2)[:, None], jnp.arange(16)[None]
        return sum(w[..., k:k + 1] * out[e[..., k], b, t] for k in range(2))

    want = dense(p, x)
    assert np.all(np.abs(np.asarray(y)).sum(-1) > 0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_yarn_frequencies_and_scale():
    """(f) Published YaRN (factor 40, beta 32 / 1 over 4096 positions):
    lanes below the correction range keep the base frequency, lanes above
    it are divided by the factor; the softmax scale is 192^-0.5 m^2 with
    m = 0.1 * 0.707 * ln 40 + 1."""
    m = D.full().mla
    inv = L.mla_inv_freq(m)
    base = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    assert np.all((inv[11:23] < base[11:23]) & (inv[11:23] > base[11:23] / 40))
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert L.mla_softmax_scale(m) == pytest.approx(192 ** -0.5 * mscale ** 2)
    np.testing.assert_allclose(R.yarn_inv_freq(m), inv, rtol=1e-6)
    assert R.softmax_scale(m) == pytest.approx(L.mla_softmax_scale(m))


def test_contiguous_path_refuses_mla():
    """(g) The contiguous cache holds per-head K/V: it refuses MLA with the
    reason instead of mis-serving it, and LMEngine serves MLA paged."""
    cfg = D.smoke()
    params, _ = T.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="latent"):
        T.init_cache(cfg, 2, 16)
    with pytest.raises(ValueError, match="paged"):
        ContiguousKV(cfg, params, 2, 16)
    assert LMEngine(cfg, params, slots=2, max_len=16).snapshot()["paged"]


def test_full_config_counts():
    """The served share holds 3.11 B parameters (8 of 64 experts); the
    whole model counts the published 15.7 B."""
    here, _ = T.count_params_cfg(D.full())
    whole, _ = T.count_params_cfg(D.full(ep=1))
    assert here == 3_110_989_312
    assert 15.6e9 < whole < 15.8e9
