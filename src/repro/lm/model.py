"""Paged transformer entry points: decode + chunked prefill over a KV pool.

Mirrors :func:`repro.nn.transformer.decode_step`'s scan-over-periods
assembly but threads the stacked KV *pool* (shared physical blocks) plus a
block ``table``/``kv_lens`` pair instead of a per-row contiguous cache.
Two entry points:

  * :func:`decode_step_paged` — one token for every slot; KV writes land at
    ``table[row, len // bs]`` (trash block for inactive rows), attention
    runs through the paged flash-decode kernel;
  * :func:`prefill_chunk_paged` — a static-width prompt chunk for ONE slot:
    one dispatch per chunk instead of one per token, causally masked per
    query so the emitted logits equal the token-by-token path.

Paging is supported for attention-only stacks (any MLP/MoE ffn half), GQA
or latent (MLA): an MLA layer's pool leaf is one ``[blocks, bs,
latent_dim]`` array of cached latents (``{"lat": ...}``) beside GQA's
``{"k", "v"}``; prefill attends non-absorbed, decode absorbed through the
latent flash-decode kernel.  Leading dense layers (``cfg.first_dense``)
run before the scan with pools of their own.  Stateful-block patterns
(mamba / xLSTM / cross-attention / encoders) keep the contiguous path —
:func:`check_paging_supported` rejects them with the reason rather than
mis-serving.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn import layers as L
from repro.nn import moe as Moe
from repro.nn import transformer as T


def paging_unsupported_reason(cfg) -> str | None:
    """None when ``cfg`` can serve paged, else a human-readable reason."""
    bad = [k for k in cfg.block_pattern
           if not k.startswith("attn") or "cross" in k]
    if bad:
        return (f"paged serving needs attention-only block patterns, got "
                f"{cfg.block_pattern} (unsupported: {bad})")
    if cfg.encoder is not None:
        return "encoder-decoder (whisper) stacks are not paged"
    if cfg.mrope_sections is not None:
        return "M-RoPE (multi-stream positions) is not paged"
    if cfg.vision_patches:
        return "vision-prefix stacks are not paged"
    if cfg.mla is not None and cfg.kv_cache_dtype == "int8":
        return "the latent (MLA) pool is bf16; an int8 latent is not served"
    return None


def check_paging_supported(cfg) -> None:
    reason = paging_unsupported_reason(cfg)
    if reason is not None:
        raise ValueError(reason)


def _layer_pool(cfg, num_blocks: int, block_size: int):
    if cfg.mla is not None:
        return {"self": L.init_latent_pool(num_blocks, block_size, cfg.mla)}
    dtype = jnp.int8 if cfg.kv_cache_dtype == "int8" else jnp.bfloat16
    return {"self": L.init_kv_pool(num_blocks, block_size, cfg.attn_cfg(),
                                   dtype)}


def init_pool(cfg, num_blocks: int, block_size: int):
    """``{"blocks": per-period pools, "lead": [one per leading layer]}``.
    The period pools mirror :func:`transformer.init_cache`: every leaf is
    ``[P, num_blocks + 1, block_size, ...]`` (the +1 is the per-layer trash
    block)."""
    check_paging_supported(cfg)
    layer = jax.eval_shape(lambda: _layer_pool(cfg, num_blocks, block_size))
    # pools start zeroed: made at their stacked size, never as a layer
    # broadcast and copied (two pools at once would not fit beside a
    # full-size model)
    blocks = [jax.tree.map(
        lambda leaf: jnp.zeros((cfg.n_periods,) + leaf.shape, leaf.dtype),
        layer) for _ in cfg.block_pattern]
    return {"blocks": blocks,
            "lead": [_layer_pool(cfg, num_blocks, block_size)
                     for _ in range(cfg.first_dense)]}


def _ffn_half(p, kind: str, cfg, x):
    """(x + ffn(x), held picks per token [B, S] int32)."""
    h = T._norm(cfg, p["ln2"], x)
    held = jnp.zeros(x.shape[:2], jnp.int32)
    if kind.endswith("moe"):
        m, aux = Moe.moe(p["moe"], h, cfg.moe)
        held = aux.get("held_picks", held)
    elif cfg.mlp_kind == "swiglu":
        m = L.swiglu(p["mlp"], h)
    else:
        m = L.gelu_mlp(p["mlp"], h)
    return x + m, held


def _stack(params, cfg, pool, x, attend):
    """Run the leading layers, then the scanned periods: ``attend(p_attn,
    h, pool_self, layer) -> (a, new_self)``.  The periods' pools ride in the
    scan's carry whole and each layer writes its entries at ``layer``, in
    place: a stacked copy of the pool per step would not fit beside a
    full-size model.  Returns (final-normed x, new pool, held picks per
    token [B, S])."""
    held = jnp.zeros(x.shape[:2], jnp.int32)
    new_lead = []
    for lp, lc in zip(params.get("lead", ()), pool["lead"]):
        a, new_self = attend(lp["attn"], T._norm(cfg, lp["ln1"], x),
                             lc["self"], None)
        x, _ = _ffn_half(lp, "attn_mlp", cfg, x + a)
        new_lead.append({**lc, "self": new_self})

    def period_body(carry, pp):
        x, held, pools, layer = carry
        pools = list(pools)
        for bi, kind in enumerate(cfg.block_pattern):
            a, new_self = attend(pp[bi]["attn"],
                                 T._norm(cfg, pp[bi]["ln1"], x),
                                 pools[bi]["self"], layer)
            x, n = _ffn_half(pp[bi], kind, cfg, x + a)
            held = held + n
            pools[bi] = {**pools[bi], "self": new_self}
        return (x, held, pools, layer + 1), None

    (x, held, blocks, _), _ = jax.lax.scan(
        period_body, (x, held, pool["blocks"], jnp.int32(0)),
        params["blocks"])
    x = T._norm(cfg, params["final_ln"], x)
    return x, {"blocks": blocks, "lead": new_lead}, held


def _logits(params, cfg, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head.astype(cfg.activ_dtype)).astype(jnp.float32)


def decode_step_paged(params, cfg, pool, table, kv_lens, tokens, active, *,
                      use_flash: bool = True, interpret: bool | None = None):
    """One decode step. tokens [B, 1]; table [B, W] int32; kv_lens [B]
    int32 pre-write lengths; active [B] bool.  Returns (logits [B, 1, V]
    f32, new_pool, held [B] int32: each row's token-expert picks on the
    experts held here, summed over layers; 0 without an expert share)."""
    def attend(p, h, ps, layer):
        if cfg.mla is not None:
            return L.mla_decode_paged(p, h, ps, cfg.mla, table, kv_lens,
                                      active, use_flash=use_flash,
                                      interpret=interpret, layer=layer)
        return L.attention_decode_paged(p, h, ps, cfg.attn_cfg(), table,
                                        kv_lens, active, use_flash=use_flash,
                                        interpret=interpret, layer=layer)

    x, new_pool, held = _stack(params, cfg, pool, T._embed(params, cfg,
                                                            tokens), attend)
    return _logits(params, cfg, x), new_pool, held[:, 0]


def prefill_chunk_paged(params, cfg, pool, row_table, len0, tokens, count):
    """Prefill one static-width chunk for one slot.  tokens [1, C] (first
    ``count`` real, tail padded); row_table [W] int32; len0 scalar int32.
    Returns (logits [1, V] f32 of the chunk's last real position
    ``count - 1``, new_pool): the LM head runs for that row alone."""
    def attend(p, h, ps, layer):
        if cfg.mla is not None:
            return L.mla_prefill_paged(p, h, ps, cfg.mla, row_table, len0,
                                       count, layer)
        return L.attention_prefill_paged(p, h, ps, cfg.attn_cfg(), row_table,
                                         len0, count, layer)

    x, new_pool, _ = _stack(params, cfg, pool, T._embed(params, cfg, tokens),
                            attend)
    last = jax.lax.dynamic_slice_in_dim(x, count - 1, 1, axis=1)
    return _logits(params, cfg, last)[:, 0], new_pool
