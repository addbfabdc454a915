"""Core transformer building blocks: norms, RoPE/M-RoPE/YaRN, GQA and
latent (MLA) attention, MLPs.

Pure-JAX pytree modules.  Every `init_*` returns `(params, logical)` where
`logical` mirrors the params tree with logical-axis tuples for sharding
(see nn/common.py).  Attention supports three modes:

  * train/prefill: causal flash-style attention (lax.scan over KV blocks,
    O(S * block) memory — required for the 32k prefill cells);
  * decode: single-token query against a KV cache (dynamic_update_slice);
  * encoder (whisper): non-causal full attention.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn.common import shard


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}, {"scale": ("embed_act",)}


def rmsnorm(p, x, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


def init_layernorm(d: int):
    return ({"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)},
            {"scale": ("embed_act",), "bias": ("embed_act",)})


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 1e4) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """x: [B, S, H, dh]; positions: [B, S] int -> rotated x."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # [dh/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, dh/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, sections: tuple,
                theta: float = 1e6) -> jax.Array:
    """Qwen2-VL multimodal RoPE: 3 position streams (t, h, w) own disjoint
    frequency sections of the head dim.  positions3: [B, 3, S]; sections sum
    to dh/2 (e.g. (16, 24, 24) for dh=128)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # [dh/2]
    # section id per frequency -> which position stream drives it
    sec_id = jnp.repeat(jnp.arange(3), jnp.array(sections), total_repeat_length=dh // 2)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(sec_id[None, :, None], (x.shape[0], dh // 2, positions3.shape[-1])),
        axis=1)  # [B, dh/2, S]
    ang = jnp.einsum("bfs,f->bsf", pos, freqs)  # [B, S, dh/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense projections
# ---------------------------------------------------------------------------

def _dense_init(key, d_in, d_out, logical, bias=False, scale=None):
    scale = scale if scale is not None else (1.0 / d_in) ** 0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * scale).astype(jnp.float32)}
    lg = {"w": logical}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
        lg["b"] = (logical[-1],)
    return p, lg


def dense(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int | None = None
    qkv_bias: bool = False
    rope_theta: float = 1e4
    mrope_sections: tuple | None = None  # set for qwen2-vl
    causal: bool = True
    flash_block: int = 1024

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads


def init_attention(key, cfg: AttnConfig):
    dh = cfg.dh
    ks = jax.random.split(key, 4)
    p, lg = {}, {}
    p["q"], lg["q"] = _dense_init(ks[0], cfg.d_model, cfg.n_heads * dh,
                                  ("embed", "heads"), bias=cfg.qkv_bias)
    p["k"], lg["k"] = _dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * dh,
                                  ("embed", "kv_heads"), bias=cfg.qkv_bias)
    p["v"], lg["v"] = _dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * dh,
                                  ("embed", "kv_heads"), bias=cfg.qkv_bias)
    p["o"], lg["o"] = _dense_init(ks[3], cfg.n_heads * dh, cfg.d_model,
                                  ("heads", "embed"))
    return p, lg


def _qkv(p, x, cfg: AttnConfig, positions):
    B, S, _ = x.shape
    dh = cfg.dh
    q = dense(p["q"], x).reshape(B, S, cfg.n_heads, dh)
    k = dense(p["k"], x).reshape(B, S, cfg.n_kv_heads, dh)
    v = dense(p["v"], x).reshape(B, S, cfg.n_kv_heads, dh)
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    elif positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # Only the q heads get an explicit constraint; k/v inherit the weight
    # sharding (forcing n_kv < mesh axis size causes involuntary resharding).
    q = shard(q, "batch", "seq", "heads", None)
    return q, k, v


def flash_attention(q, k, v, *, causal: bool, block: int, q_offset=0,
                    scale: float | None = None) -> jax.Array:
    """Blockwise-softmax attention: lax.scan over KV blocks, O(S*block) memory.

    q, k: [B, S, H|G, dh]; v: [B, Sk, G, dv] with H = G * rep (GQA); ``dv``
    may differ from ``dh`` (MLA) and ``scale`` defaults to ``dh ** -0.5``.
    KV heads are repeated up to H *inside* the kernel so every intermediate
    carries a plain heads axis — the layout that shards cleanly over
    `model` (grouped [.., G, rep, ..] layouts make GSPMD fall back to
    replication).
    """
    B, Sq, H, dh = q.shape
    Sk, G = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    rep = H // G
    scale = dh ** -0.5 if scale is None else scale
    qf = (q.astype(jnp.float32) * scale)
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)  # [B, Sk, H, dh]
        v = jnp.repeat(v, rep, axis=2)
    pad = (-Sk) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = k.shape[1] // block
    kb = jnp.moveaxis(k.reshape(B, nb, block, H, dh), 1, 0)  # [nb, B, blk, H, dh]
    vb = jnp.moveaxis(v.reshape(B, nb, block, H, dv), 1, 0)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc, j = carry
        kj, vj = inp
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, kj.astype(jnp.float32))
        s = shard(s, "batch", "seq", "heads", None)
        kv_pos = j * block + jnp.arange(block)
        mask = kv_pos[None, :] <= q_pos[:, None] if causal else \
            jnp.ones((Sq, block), bool)
        valid = (kv_pos < Sk)[None, :]
        s = jnp.where((mask & valid)[None, :, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new, j + 1), None

    m0 = jnp.full((B, Sq, H), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Sq, H), jnp.float32)
    a0 = jnp.zeros((B, Sq, H, dv), jnp.float32)
    # checkpoint the block body: without it the scan saves the [.., block]
    # probability tensor for EVERY block for the backward pass (O(S^2) memory,
    # defeating the point of the streaming formulation).
    (m, l, acc, _), _ = jax.lax.scan(jax.checkpoint(body),
                                     (m0, l0, a0, jnp.int32(0)), (kb, vb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, dv).astype(q.dtype)


def attention(p, x, cfg: AttnConfig, positions=None) -> jax.Array:
    """Full-sequence (train / prefill / encoder) attention."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    out = flash_attention(q, k, v, causal=cfg.causal, block=min(cfg.flash_block, S))
    out = out.reshape(B, S, cfg.n_heads * cfg.dh)
    return shard(dense(p["o"], out), "batch", "seq", "embed_act")


def _quant_kv(t: jax.Array):
    """Per-(token, head) symmetric int8 quantisation of a [B, 1, G, dh] slab."""
    amax = jnp.max(jnp.abs(t), axis=-1, keepdims=True)
    scale = amax.astype(jnp.float32) / 127.0 + 1e-9
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def attention_decode(p, x, cache: dict, cfg: AttnConfig, positions) -> tuple:
    """Single-token decode. x: [B, 1, d]; cache: {'k','v': [B, Smax, G, dh],
    'len': [B]} (+ 'k_scale','v_scale' when int8). Returns (out, new_cache).

    With an int8 cache (beyond-paper optimization; the paper's Sec. IV-B
    low-precision insight applied to the LM substrate) the dominant decode
    HBM traffic — cache reads — halves vs bf16.
    """
    B = x.shape[0]
    q, k_new, v_new = _qkv(p, x, cfg, positions)
    pos = cache["len"]  # [B] — rows may sit at different lengths under
    # continuous batching (per-slot prefill), so writes and masks are per-row
    rows = jnp.arange(B)
    quantized = cache["k"].dtype == jnp.int8
    new_cache = dict(cache)
    if quantized:
        kq, ks = _quant_kv(k_new)
        vq, vs = _quant_kv(v_new)
        for name, val in (("k", kq), ("v", vq), ("k_scale", ks), ("v_scale", vs)):
            new_cache[name] = cache[name].at[rows, pos].set(
                val[:, 0].astype(cache[name].dtype))
        k = new_cache["k"].astype(jnp.float32) * new_cache["k_scale"]
        v = new_cache["v"].astype(jnp.float32) * new_cache["v_scale"]
    else:
        for name, val in (("k", k_new), ("v", v_new)):
            new_cache[name] = cache[name].at[rows, pos].set(
                val[:, 0].astype(cache[name].dtype))
        k, v = new_cache["k"], new_cache["v"]
    Smax, G = k.shape[1], k.shape[2]
    rep = cfg.n_heads // G
    scale = cfg.dh ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, 1, G, rep, cfg.dh)
    s = jnp.einsum("bqgrd,bkgd->bqgrk", qf, k.astype(jnp.float32))
    valid = jnp.arange(Smax)[None, :] <= pos[:, None]  # [B, Smax]
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqgrk,bkgd->bqgrd", w, v.astype(jnp.float32))
    out = out.reshape(B, 1, cfg.n_heads * cfg.dh).astype(x.dtype)
    new_cache["len"] = cache["len"] + 1
    return shard(dense(p["o"], out), "batch", None, "embed_act"), new_cache


def init_kv_cache(batch: int, max_len: int, cfg: AttnConfig, dtype=jnp.bfloat16):
    G, dh = cfg.n_kv_heads, cfg.dh
    cache = {"k": jnp.zeros((batch, max_len, G, dh), dtype),
             "v": jnp.zeros((batch, max_len, G, dh), dtype),
             "len": jnp.zeros((batch,), jnp.int32)}
    if dtype == jnp.int8:
        cache["k_scale"] = jnp.zeros((batch, max_len, G, 1), jnp.float32)
        cache["v_scale"] = jnp.zeros((batch, max_len, G, 1), jnp.float32)
    return cache


# ---------------------------------------------------------------------------
# Paged KV attention (block-table pool; see repro.lm.paging)
# ---------------------------------------------------------------------------

def init_kv_pool(num_blocks: int, block_size: int, cfg: AttnConfig,
                 dtype=jnp.bfloat16):
    """Shared KV block pool: ``num_blocks`` live blocks plus ONE trash block
    at physical index ``num_blocks`` — KV writes for inactive rows and
    padded prefill tokens scatter there instead of needing a where-merge
    over the whole pool.  Blocks are reused without zeroing: the per-row
    ``kv_lens`` masks make stale positions unreachable."""
    G, dh = cfg.n_kv_heads, cfg.dh
    nbp = num_blocks + 1
    pool = {"k": jnp.zeros((nbp, block_size, G, dh), dtype),
            "v": jnp.zeros((nbp, block_size, G, dh), dtype)}
    if dtype == jnp.int8:
        pool["k_scale"] = jnp.zeros((nbp, block_size, G, 1), jnp.float32)
        pool["v_scale"] = jnp.zeros((nbp, block_size, G, 1), jnp.float32)
    return pool


def _at(leaf, layer, *idx):
    """``leaf.at[...]`` of one layer's block entries: pool leaves carry a
    leading layer axis when ``layer`` is given (the whole stack's pool,
    updated in place), none when it is ``None``."""
    return leaf.at[idx if layer is None else (layer,) + idx]


def _blocks(leaf, layer, idx):
    """The blocks ``idx`` of one layer's pool leaf (see :func:`_at`)."""
    return leaf[idx] if layer is None else leaf[layer, idx]


def _pool_write(pool: dict, phys, off, k_new, v_new, layer=None):
    """Scatter one token per row into the pool at (phys[r], off[r]).
    k_new/v_new: [R, G, dh] (one token per row, any leading row count)."""
    quantized = pool["k"].dtype == jnp.int8
    new_pool = dict(pool)
    if quantized:
        kq, ks = _quant_kv(k_new)
        vq, vs = _quant_kv(v_new)
        vals = (("k", kq), ("v", vq), ("k_scale", ks), ("v_scale", vs))
    else:
        vals = (("k", k_new), ("v", v_new))
    for name, val in vals:
        new_pool[name] = _at(pool[name], layer, phys, off).set(
            val.astype(pool[name].dtype))
    return new_pool


def attention_decode_paged(p, x, pool: dict, cfg: AttnConfig, table, kv_lens,
                           active, *, use_flash: bool = True,
                           interpret: bool | None = None,
                           layer=None) -> tuple:
    """Single-token decode against a paged KV pool.

    x: [B, 1, d]; pool: {'k','v': [NBP, bs, G, dh]} (+ scales when int8),
    or with a leading layer axis read and written at ``layer``;
    table: [B, W] int32 block table; kv_lens: [B] int32 pre-write lengths;
    active: [B] bool — inactive rows write their KV to the trash block (and
    their output is garbage the caller ignores).  Returns (out, new_pool).
    """
    from repro.kernels.flash_decode import ops as _fd

    B = x.shape[0]
    q, k_new, v_new = _qkv(p, x, cfg, kv_lens[:, None])
    _, bs, G, _ = pool["k"].shape[-4:]
    trash = pool["k"].shape[-4] - 1
    W = table.shape[1]
    rows = jnp.arange(B)
    blk = jnp.minimum(kv_lens // bs, W - 1)
    phys = jnp.where(active, table[rows, blk], trash)
    off = kv_lens % bs
    new_pool = _pool_write(pool, phys, off, k_new[:, 0], v_new[:, 0], layer)
    rep = cfg.n_heads // G
    qf = (q.astype(jnp.float32) * cfg.dh ** -0.5).reshape(B, G, rep, cfg.dh)
    out = _fd.flash_decode(qf, new_pool, table, kv_lens + 1,
                           use_flash=use_flash, interpret=interpret,
                           layer=layer)
    out = out.reshape(B, 1, cfg.n_heads * cfg.dh).astype(x.dtype)
    return shard(dense(p["o"], out), "batch", None, "embed_act"), new_pool


def attention_prefill_paged(p, x, pool: dict, cfg: AttnConfig, row_table,
                            len0, count, layer=None) -> tuple:
    """Chunked prefill for ONE slot against the paged pool.

    x: [1, C, d] — a static-width chunk whose first ``count`` tokens are
    real (the tail is padding whose KV scatters to the trash block);
    row_table: [W] int32; len0: scalar int32 KV length before the chunk.
    Causal masking is per query position (kv pos <= len0 + i), so one
    dispatch replaces C single-token decode dispatches with identical
    logits.  Returns (out [1, C, d], new_pool).
    """
    C = x.shape[1]
    idx = len0 + jnp.arange(C)                       # absolute positions [C]
    q, k_new, v_new = _qkv(p, x, cfg, idx[None])
    bs = pool["k"].shape[-3]
    trash = pool["k"].shape[-4] - 1
    W = row_table.shape[0]
    within = jnp.arange(C) < count
    phys = jnp.where(within, row_table[jnp.minimum(idx // bs, W - 1)], trash)
    new_pool = _pool_write(pool, phys, idx % bs, k_new[0], v_new[0], layer)
    k = _blocks(new_pool["k"], layer, row_table).astype(jnp.float32)
    v = _blocks(new_pool["v"], layer, row_table).astype(jnp.float32)
    if "k_scale" in new_pool:  # [W, bs, G, dh]
        k = k * _blocks(new_pool["k_scale"], layer, row_table)
        v = v * _blocks(new_pool["v_scale"], layer, row_table)
    G, dh = k.shape[2], k.shape[3]
    k = k.reshape(W * bs, G, dh)
    v = v.reshape(W * bs, G, dh)
    rep = cfg.n_heads // G
    qf = (q.astype(jnp.float32) * cfg.dh ** -0.5).reshape(1, C, G, rep, dh)
    s = jnp.einsum("bcgrd,kgd->bcgrk", qf, k)
    valid = jnp.arange(W * bs)[None, :] <= idx[:, None]  # [C, W*bs]
    s = jnp.where(valid[None, :, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bcgrk,kgd->bcgrd", w, v)
    out = out.reshape(1, C, cfg.n_heads * cfg.dh).astype(x.dtype)
    return shard(dense(p["o"], out), "batch", "seq", "embed_act"), new_pool


# ---------------------------------------------------------------------------
# Multi-head latent attention (MLA, DeepSeek-V2 arXiv:2405.04434 §2.1)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Latent attention without a query low-rank (DeepSeek-V2-Lite's form).

    Keys and values come from one per-token latent: ``c = RMSNorm(x W_DKV)``
    (``kv_lora_rank`` wide) and a decoupled, roped key ``k_R = RoPE(x W_KR)``
    (``qk_rope_head_dim`` wide) shared by every head.  The paged cache holds
    ``[c, k_R]``, one ``latent_dim``-wide entry per token and layer.  RoPE is
    YaRN-scaled when ``rope_factor > 1`` (DeepSeek-V2's YaRN rotary
    embedding: frequencies blended between interpolation and extrapolation
    over the correction range of ``beta_fast`` / ``beta_slow`` rotations at
    ``rope_original_max`` positions, softmax scale times ``mscale(factor,
    mscale_all_dim)**2``).
    """

    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 1e4
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    eps: float = 1e-6

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Width of one cached token: the latent plus the roped key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """Lanes of one pool entry: ``latent_dim`` padded with zeros to a
        multiple of 128, the TPU's lane tile."""
        return -(-self.latent_dim // 128) * 128


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, base: float,
                         max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))) / \
        (2 * math.log(base))


def mla_inv_freq(cfg: MLAConfig) -> np.ndarray:
    """[qk_rope_head_dim / 2] f32 inverse frequencies of the roped lanes.

    Lanes below the correction range keep the base frequency
    (extrapolation), lanes above it are divided by ``rope_factor``
    (interpolation), with a linear ramp between.
    """
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return extra.astype(np.float32)
    lo = max(math.floor(_yarn_correction_dim(
        cfg.beta_fast, dim, base, cfg.rope_original_max)), 0)
    hi = min(math.ceil(_yarn_correction_dim(
        cfg.beta_slow, dim, base, cfg.rope_original_max)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: extrapolate, 0: interpolate
    return (extra / cfg.rope_factor * (1 - keep) + extra * keep).astype(
        np.float32)


def mla_softmax_scale(cfg: MLAConfig) -> float:
    m = yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim) \
        if cfg.mscale_all_dim else 1.0
    return cfg.q_head_dim ** -0.5 * m * m


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           inv_freq) -> jax.Array:
    """HF ``modeling_deepseek``'s rotation: the lanes of ``x [..., S, H, d]``
    are de-interleaved (even lanes, then odd) and rotated as halves.  The
    YaRN cos/sin scale mscale(factor, mscale) / mscale(factor,
    mscale_all_dim) is 1 for DeepSeek-V2 and is left out."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, d/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def init_mla(key, cfg: MLAConfig):
    H, r = cfg.n_heads, cfg.kv_lora_rank
    ks = jax.random.split(key, 5)
    p, lg = {}, {}
    p["q"], lg["q"] = _dense_init(ks[0], cfg.d_model, H * cfg.q_head_dim,
                                  ("embed", "heads"))
    p["kv_a"], lg["kv_a"] = _dense_init(ks[1], cfg.d_model, cfg.latent_dim,
                                        ("embed", None))
    p["kv_ln"], lg["kv_ln"] = init_rmsnorm(r)
    lg["kv_ln"] = {"scale": (None,)}
    # kv_b of the published checkpoint, split by what it makes: W_UK gives
    # the per-head non-roped key, W_UV the per-head value
    p["uk"], lg["uk"] = _dense_init(ks[2], r, H * cfg.qk_nope_head_dim,
                                    (None, "heads"))
    p["uv"], lg["uv"] = _dense_init(ks[3], r, H * cfg.v_head_dim,
                                    (None, "heads"))
    p["o"], lg["o"] = _dense_init(ks[4], H * cfg.v_head_dim, cfg.d_model,
                                  ("heads", "embed"))
    return p, lg


def mla_query(p, x, cfg: MLAConfig, positions):
    """(q_nope [B, S, H, nope], roped q_pe [B, S, H, rope])."""
    B, S, _ = x.shape
    q = dense(p["q"], x).reshape(B, S, cfg.n_heads, cfg.q_head_dim)
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    return q_nope, apply_rope_interleaved(q_pe, positions, mla_inv_freq(cfg))


def mla_latent(p, x, cfg: MLAConfig, positions):
    """The cached entry of each token: ``[RMSNorm(c), RoPE(k_R)]``,
    [B, S, latent_dim] in ``x``'s dtype."""
    kv = dense(p["kv_a"], x)
    c, k_pe = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    c = rmsnorm(p["kv_ln"], c, cfg.eps)
    k_pe = apply_rope_interleaved(k_pe[:, :, None, :], positions,
                                  mla_inv_freq(cfg))[:, :, 0]
    return jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)


def mla_expand(p, lat, cfg: MLAConfig):
    """Non-absorbed keys and values from cached latents [B, Sk, latent_dim]:
    (k [B, Sk, H, q_head_dim], v [B, Sk, H, v_head_dim])."""
    B, Sk, _ = lat.shape
    H = cfg.n_heads
    c, k_pe = jnp.split(lat, [cfg.kv_lora_rank], axis=-1)
    k_nope = dense(p["uk"], c).reshape(B, Sk, H, cfg.qk_nope_head_dim)
    v = dense(p["uv"], c).reshape(B, Sk, H, cfg.v_head_dim)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :],
                            (B, Sk, H, cfg.qk_rope_head_dim))
    return jnp.concatenate([k_nope, k_pe.astype(k_nope.dtype)], axis=-1), v


def mla_attention(p, x, cfg: MLAConfig, positions) -> jax.Array:
    """Full-sequence (train / prefill) latent attention, non-absorbed:
    per-head keys and values up-projected from the latents."""
    B, S, _ = x.shape
    q_nope, q_pe = mla_query(p, x, cfg, positions)
    k, v = mla_expand(p, mla_latent(p, x, cfg, positions), cfg)
    out = flash_attention(jnp.concatenate([q_nope, q_pe], axis=-1), k, v,
                          causal=True, block=min(1024, S),
                          scale=mla_softmax_scale(cfg))
    out = out.reshape(B, S, cfg.n_heads * cfg.v_head_dim).astype(x.dtype)
    return shard(dense(p["o"], out), "batch", "seq", "embed_act")


def init_latent_pool(num_blocks: int, block_size: int, cfg: MLAConfig,
                     dtype=jnp.bfloat16):
    """The latent pool leaf ``{"lat": [num_blocks + 1, block_size,
    pool_width]}``: one entry per token serves every head's key (its
    latent_dim lanes) and value (the first ``kv_lora_rank``); the last
    block is the trash block, as in :func:`init_kv_pool`.  The entry is
    zero-padded to a multiple of 128 lanes: a TPU tiles the minor
    dimension by 128, and with 576 lanes it lays the pool out transposed
    (block positions minor), which the kernel's tiles and the per-token
    writes do not match, so every step would relayout the whole pool."""
    return {"lat": jnp.zeros((num_blocks + 1, block_size, cfg.pool_width),
                             dtype)}


def _pool_entry(lat, cfg: MLAConfig):
    """Latents [..., latent_dim] as pool entries [..., pool_width]."""
    pad = cfg.pool_width - cfg.latent_dim
    return jnp.pad(lat, [(0, 0)] * (lat.ndim - 1) + [(0, pad)])


def mla_prefill_paged(p, x, pool: dict, cfg: MLAConfig, row_table, len0,
                      count, layer=None) -> tuple:
    """Chunked prefill for ONE slot against the latent pool (see
    :func:`attention_prefill_paged` for the chunk contract).

    Attention is non-absorbed and streams the row's live blocks only: a
    loop over ``ceil((len0 + count) / bs)`` table entries up-projects each
    block's latents to per-head keys and values and folds them into an
    online softmax, queries masked causally by position.
    """
    B, C, _ = x.shape
    H = cfg.n_heads
    idx = len0 + jnp.arange(C)
    q_nope, q_pe = mla_query(p, x, cfg, idx[None])
    lat_new = mla_latent(p, x, cfg, idx[None])[0]  # [C, latent_dim]
    bs = pool["lat"].shape[-2]
    trash = pool["lat"].shape[-3] - 1
    W = row_table.shape[0]
    within = jnp.arange(C) < count
    phys = jnp.where(within, row_table[jnp.minimum(idx // bs, W - 1)], trash)
    lat_pool = _at(pool["lat"], layer, phys, idx % bs).set(
        _pool_entry(lat_new, cfg).astype(pool["lat"].dtype))
    qf = jnp.concatenate([q_nope, q_pe], axis=-1)[0].astype(jnp.float32) \
        * mla_softmax_scale(cfg)                         # [C, H, dq]

    def block(j, carry):
        m, l, acc = carry
        lat = _blocks(lat_pool, layer, row_table[j])[None, :, :cfg.latent_dim]
        k, v = mla_expand(p, lat.astype(x.dtype), cfg)   # [1, bs, H, .]
        s = jnp.einsum("qhd,khd->hqk", qf, k[0].astype(jnp.float32))
        kv_pos = j * bs + jnp.arange(bs)
        s = jnp.where(kv_pos[None, None, :] <= idx[None, :, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        pr = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        acc = acc * corr[..., None] + jnp.einsum(
            "hqk,khd->hqd", pr, v[0].astype(jnp.float32))
        return m_new, l * corr + jnp.sum(pr, axis=-1), acc

    live = jnp.minimum((len0 + count + bs - 1) // bs, W)
    m0 = jnp.full((H, C), -1e30, jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        0, live, block, (m0, jnp.zeros((H, C), jnp.float32),
                         jnp.zeros((H, C, cfg.v_head_dim), jnp.float32)))
    out = (acc / jnp.maximum(l[..., None], 1e-30)).transpose(1, 0, 2)
    out = out.reshape(B, C, H * cfg.v_head_dim).astype(x.dtype)
    return shard(dense(p["o"], out), "batch", "seq", "embed_act"), \
        {"lat": lat_pool}


def mla_absorbed_query(p, q_nope, q_pe, cfg: MLAConfig):
    """Decode's query in latent space: ``[q_nope W_UK^T, q_pe]`` per head,
    scaled by the softmax scale, f32 [B, H, latent_dim]."""
    B, H = q_nope.shape[0], cfg.n_heads
    uk = p["uk"]["w"].astype(jnp.float32).reshape(
        cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope.reshape(B, H, -1).astype(
        jnp.float32), uk)
    q = jnp.concatenate([q_lat, q_pe.reshape(B, H, -1).astype(jnp.float32)],
                        axis=-1)
    return q * mla_softmax_scale(cfg)


def mla_absorbed_out(p, o_lat, x, cfg: MLAConfig):
    """Attention output from the latent context [B, H, kv_lora_rank]:
    ``W_UV`` per head, then ``W_O``."""
    B, H = o_lat.shape[0], cfg.n_heads
    uv = p["uv"]["w"].astype(jnp.float32).reshape(
        cfg.kv_lora_rank, H, cfg.v_head_dim)
    out = jnp.einsum("bhc,chd->bhd", o_lat, uv)
    out = out.reshape(B, 1, H * cfg.v_head_dim).astype(x.dtype)
    return shard(dense(p["o"], out), "batch", None, "embed_act")


def mla_decode_paged(p, x, pool: dict, cfg: MLAConfig, table, kv_lens,
                     active, *, use_flash: bool = True,
                     interpret: bool | None = None, layer=None) -> tuple:
    """Single-token decode against the latent pool, absorbed: ``W_UK`` is
    folded into the query and ``W_UV`` into the output, so attention runs
    over the cached latents directly (one 576-wide entry per position for
    all heads).  Shapes as :func:`attention_decode_paged`."""
    from repro.kernels.flash_decode import ops as _fd

    B = x.shape[0]
    q_nope, q_pe = mla_query(p, x, cfg, kv_lens[:, None])
    lat_new = mla_latent(p, x, cfg, kv_lens[:, None])[:, 0]
    bs = pool["lat"].shape[-2]
    trash = pool["lat"].shape[-3] - 1
    W = table.shape[1]
    blk = jnp.minimum(kv_lens // bs, W - 1)
    phys = jnp.where(active, table[jnp.arange(B), blk], trash)
    new_pool = {"lat": _at(pool["lat"], layer, phys, kv_lens % bs).set(
        _pool_entry(lat_new, cfg).astype(pool["lat"].dtype))}
    q = _pool_entry(mla_absorbed_query(p, q_nope, q_pe, cfg), cfg)
    o_lat = _fd.flash_decode(q, new_pool, table, kv_lens + 1,
                             use_flash=use_flash, interpret=interpret,
                             v_width=cfg.kv_lora_rank, layer=layer)
    return mla_absorbed_out(p, o_lat, x, cfg), new_pool


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int):
    ks = jax.random.split(key, 3)
    p, lg = {}, {}
    p["gate"], lg["gate"] = _dense_init(ks[0], d_model, d_ff, ("embed", "mlp"))
    p["up"], lg["up"] = _dense_init(ks[1], d_model, d_ff, ("embed", "mlp"))
    p["down"], lg["down"] = _dense_init(ks[2], d_ff, d_model, ("mlp", "embed"))
    return p, lg


def swiglu(p, x):
    h = jax.nn.silu(dense(p["gate"], x)) * dense(p["up"], x)
    h = shard(h, "batch", "seq", "mlp")
    return shard(dense(p["down"], h), "batch", "seq", "embed_act")


def init_gelu_mlp(key, d_model: int, d_ff: int, bias: bool = True):
    ks = jax.random.split(key, 2)
    p, lg = {}, {}
    p["up"], lg["up"] = _dense_init(ks[0], d_model, d_ff, ("embed", "mlp"), bias=bias)
    p["down"], lg["down"] = _dense_init(ks[1], d_ff, d_model, ("mlp", "embed"), bias=bias)
    return p, lg


def gelu_mlp(p, x):
    h = jax.nn.gelu(dense(p["up"], x))
    h = shard(h, "batch", "seq", "mlp")
    return shard(dense(p["down"], h), "batch", "seq", "embed_act")
