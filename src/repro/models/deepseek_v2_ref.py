"""Plain reference forward of DeepSeek-V2(-Lite), written from the paper's
equations (arXiv:2405.04434 §2.1 MLA, §2.2 DeepSeekMoE) and HF
``modeling_deepseek``: f32 arithmetic at ``HIGHEST`` matmul precision, no
kernel, no cache, one sequence at a time.

It reads the serving program's parameter tree (:func:`repro.nn.transformer.
init` of a ``deepseek_v2_lite`` config) and casts each layer's weights to
f32 as it reaches them: the cast of bf16 weights is exact, and an f32 copy
of the whole model would not fit one chip.  Causal attention runs in query
blocks of ``q_block`` positions (the sequence length a multiple of it),
each over the key blocks up to it with an exact softmax accumulated block
by block, so that a long sequence fits.

Departures from the published description:

* the expert share: only the routed experts the configuration holds
  (``MoEConfig.held`` from ``held_from``) contribute, as on the serving
  chip; the router still scores all ``num_experts``;
* ``kv_b`` is kept as its two halves, ``W_UK`` and ``W_UV``, and the RoPE
  lanes of ``q_pe`` / ``k_R`` are de-interleaved as HF does before the
  rotation; both are layouts, not different mathematics;
* the YaRN cos/sin scale mscale(factor, mscale) / mscale(factor,
  mscale_all_dim) is 1 for DeepSeek-V2 and is not applied;
* logits are computed only at the positions asked for.

``latent_dtype`` rounds the cached latent ``[c, k_R]`` to that dtype
before attention (the benchmark's precision control).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _w(leaf):
    return leaf.astype(F32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(scale)


def yarn_inv_freq(m) -> np.ndarray:
    """DeepSeek-V2's YaRN rotary frequencies for the ``qk_rope_head_dim``
    lanes of MLA config ``m``."""
    dim, base = m.qk_rope_head_dim, m.rope_theta
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / pos_freqs, 1.0 / (m.rope_factor * pos_freqs)
    if m.rope_factor <= 1:
        return extra.astype(np.float32)

    def corr(rot):
        return dim * math.log(m.rope_original_max / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(m.beta_fast)), 0)
    high = min(math.ceil(corr(m.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(m) -> float:
    mscale = 0.1 * m.mscale_all_dim * math.log(m.rope_factor) + 1.0 \
        if m.rope_factor > 1 and m.mscale_all_dim else 1.0
    return (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5 * mscale ** 2


def rope(x, pos, inv_freq):
    """x [S, ..., d]: even lanes then odd lanes, rotated as halves."""
    ang = pos[:, None].astype(F32) * inv_freq[None, :]  # [S, d/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(p, x, pos, m, latent_dtype=None, q_block: int = 1024):
    """Causal multi-head latent attention of x [S, d], non-absorbed."""
    S, H = x.shape[0], m.n_heads
    nope, rd, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.kv_lora_rank
    inv = jnp.asarray(yarn_inv_freq(m))
    q = _mm(x, _w(p["q"]["w"])).reshape(S, H, nope + rd)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, inv)], -1)
    kv = _mm(x, _w(p["kv_a"]["w"]))
    c = rms_norm(kv[:, :r], p["kv_ln"]["scale"], m.eps)
    k_r = rope(kv[:, r:], pos, inv)
    if latent_dtype is not None:
        c = c.astype(latent_dtype).astype(F32)
        k_r = k_r.astype(latent_dtype).astype(F32)
    k = jnp.concatenate([
        _mm(c, _w(p["uk"]["w"])).reshape(S, H, nope),
        jnp.broadcast_to(k_r[:, None, :], (S, H, rd))], -1)
    v = _mm(c, _w(p["uv"]["w"])).reshape(S, H, m.v_head_dim)
    scale = softmax_scale(m)
    qb = min(q_block, S)
    assert S % qb == 0, (S, qb)

    def block(_, lo):
        """Queries lo .. lo + qb over the keys up to them, a key block at a
        time (an exact softmax, accumulated online)."""
        qi = jax.lax.dynamic_slice_in_dim(q, lo, qb) * scale
        q_pos = lo + jnp.arange(qb)

        def keys(j, carry):
            mx, den, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * qb, qb)
            vj = jax.lax.dynamic_slice_in_dim(v, j * qb, qb)
            s = jnp.einsum("qhd,khd->hqk", qi, kj, precision=HI)
            causal = (j * qb + jnp.arange(qb))[None, :] <= q_pos[:, None]
            s = jnp.where(causal[None], s, -jnp.inf)
            new = jnp.maximum(mx, jnp.max(s, axis=-1))
            pr = jnp.exp(s - new[..., None])
            corr = jnp.exp(mx - new)
            return new, den * corr + jnp.sum(pr, -1), acc * corr[..., None] \
                + jnp.einsum("hqk,khd->hqd", pr, vj, precision=HI)

        init = (jnp.full((H, qb), -jnp.inf), jnp.zeros((H, qb)),
                jnp.zeros((H, qb, m.v_head_dim)))
        _, den, acc = jax.lax.fori_loop(0, lo // qb + 1, keys, init)
        return None, jnp.swapaxes(acc / den[..., None], 0, 1)

    _, o = jax.lax.scan(block, None, jnp.arange(0, S, qb))
    return _mm(o.reshape(S, H * m.v_head_dim), _w(p["o"]["w"]))


def swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, _w(p["gate"]["w"])))
               * _mm(x, _w(p["up"]["w"])), _w(p["down"]["w"]))


def moe(p, x, cfg):
    """DeepSeekMoE with this chip's expert share: softmax router over all
    experts, greedy top-k, each held expert over exactly its tokens (grouped
    matmul over the picks sorted by expert), plus the shared experts."""
    S, K = x.shape[0], cfg.top_k
    held, first = cfg.n_local, cfg.held_from
    probs = jax.nn.softmax(_mm(x, _w(p["router"])), axis=-1)
    w, e = jax.lax.top_k(probs, K)
    if cfg.norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * cfg.routed_scale
    mine = (e >= first) & (e < first + held)
    key = jnp.where(mine, e - first, held).reshape(-1)
    order = jnp.argsort(key)
    rows = order // K
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    xs = x[rows]
    gd = partial(jax.lax.ragged_dot, group_sizes=sizes, precision=HI,
                 preferred_element_type=F32)
    h = jax.nn.silu(gd(xs, _w(p["gate"]))) * gd(xs, _w(p["up"]))
    y = gd(h, _w(p["down"]))
    wk = jnp.where(mine, w, 0.0).reshape(-1)[order]
    y = jnp.where(wk[:, None] > 0, y * wk[:, None], 0.0)
    out = jnp.zeros_like(x).at[rows].add(y)
    if "shared" in p:
        out = out + swiglu(p["shared"], x)
    return out


def layer(p, x, pos, cfg, ffn: str, latent_dtype=None, q_block=1024):
    eps = cfg.mla.eps
    x = x + mla(p["attn"], rms_norm(x, p["ln1"]["scale"], eps), pos, cfg.mla,
                latent_dtype, q_block)
    h = rms_norm(x, p["ln2"]["scale"], eps)
    return x + (moe(p["moe"], h, cfg.moe) if ffn == "moe"
                else swiglu(p["mlp"], h))


def forward(params, cfg, tokens, out_pos, *, latent_dtype=None,
            q_block: int = 1024):
    """tokens [S] -> f32 logits [len(out_pos), vocab] of the next token
    after each position in ``out_pos``."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = _w(params["embed"])[tokens]
    for lp in params.get("lead", ()):
        x = layer(lp, x, pos, cfg, "mlp", latent_dtype, q_block)

    def body(x, bp):
        return layer(bp[0], x, pos, cfg, "moe", latent_dtype, q_block), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    h = rms_norm(x[out_pos], params["final_ln"]["scale"], cfg.mla.eps)
    return _mm(h, _w(params["lm_head"]))


@partial(jax.jit, static_argnames=("cfg", "latent_dtype", "q_block"))
def forward_jit(params, cfg, tokens, out_pos, latent_dtype=None,
                q_block: int = 1024):
    return forward(params, cfg, tokens, out_pos, latent_dtype=latent_dtype,
                   q_block=q_block)
