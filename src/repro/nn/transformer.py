"""Model assembly: heterogeneous-block decoder stacks with scan-over-layers.

One config drives all 10 assigned architectures.  A `block_pattern` (cycled
over layers) names each layer's kind:

    attn_mlp | attn_moe | attn_cross_mlp (whisper dec) |
    mamba_mlp | mamba_moe | mlstm | slstm

Layers are grouped into *periods* of len(block_pattern); parameters are
stacked across periods [P, ...] and the stack executes under lax.scan, so
HLO size stays O(pattern) for an 80-layer model (critical for 512-device
compile times).  Remat wraps the period body for training.

Three entry points per model: `forward` (train / prefill), `decode_step`
(one token against mutable caches), `loss_fn` (next-token CE + MoE aux).

Latent-attention stacks (``mla`` set: DeepSeek-V2) may lead with
``first_dense`` dense ``attn_mlp`` layers of width ``d_ff`` that sit
outside the scan (``params["lead"]``); the ``block_pattern`` periods follow.
They serve from the paged latent pool only (:mod:`repro.lm.model`).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.nn import layers as L
from repro.nn import mamba as Mb
from repro.nn import moe as Moe
from repro.nn import xlstm as Xl
from repro.nn.common import shard


@dataclasses.dataclass(frozen=True)
class EncoderConfig:  # whisper-style
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    n_frames: int = 1500


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    block_pattern: tuple = ("attn_mlp",)
    norm: str = "rmsnorm"  # or "layernorm"
    mlp_kind: str = "swiglu"  # or "gelu"
    qkv_bias: bool = False
    rope_theta: float = 1e4
    mrope_sections: tuple | None = None  # qwen2-vl
    vision_patches: int = 0  # qwen2-vl stub frontend: patches replace prefix tokens
    moe: Moe.MoEConfig | None = None
    mamba: Mb.MambaConfig | None = None
    xlstm: Xl.XLSTMConfig | None = None
    encoder: EncoderConfig | None = None  # whisper
    mla: L.MLAConfig | None = None  # latent attention in every attn block
    first_dense: int = 0  # leading dense attn_mlp layers, outside the scan
    tie_embeddings: bool = False
    remat: bool = True
    remat_policy: str = "full"  # full = recompute everything in the period;
    # "dots" saves matmul outputs (compute/memory trade, hillclimb knob)
    kv_cache_dtype: str = "bf16"  # "int8": halves decode cache traffic (§Perf)
    param_dtype: Any = jnp.float32
    activ_dtype: Any = jnp.bfloat16

    @property
    def period(self) -> int:
        return len(self.block_pattern)

    @property
    def n_periods(self) -> int:
        n = self.n_layers - self.first_dense
        assert n % self.period == 0, (self.n_layers, self.period)
        return n // self.period

    def attn_cfg(self, causal=True) -> L.AttnConfig:
        return L.AttnConfig(self.d_model, self.n_heads, self.n_kv_heads,
                            self.head_dim, self.qkv_bias, self.rope_theta,
                            self.mrope_sections, causal=causal)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, kind: str, cfg: ModelConfig):
    p, lg = {}, {}
    ks = jax.random.split(key, 6)
    norm_init = L.init_rmsnorm if cfg.norm == "rmsnorm" else L.init_layernorm
    if kind.startswith("attn"):
        p["ln1"], lg["ln1"] = norm_init(cfg.d_model)
        if cfg.mla is not None:
            p["attn"], lg["attn"] = L.init_mla(ks[0], cfg.mla)
        else:
            p["attn"], lg["attn"] = L.init_attention(ks[0], cfg.attn_cfg())
        if "cross" in kind:
            p["lnx"], lg["lnx"] = norm_init(cfg.d_model)
            xcfg = L.AttnConfig(cfg.d_model, cfg.n_heads, cfg.n_heads, causal=False)
            p["xattn"], lg["xattn"] = L.init_attention(ks[1], xcfg)
    elif kind.startswith("mamba"):
        p["ln1"], lg["ln1"] = norm_init(cfg.d_model)
        p["mamba"], lg["mamba"] = Mb.init_mamba(ks[0], cfg.mamba)
    elif kind == "mlstm":
        p["ln1"], lg["ln1"] = norm_init(cfg.d_model)
        p["mlstm"], lg["mlstm"] = Xl.init_mlstm(ks[0], cfg.xlstm)
        return p, lg  # xlstm blocks have no separate mlp
    elif kind == "slstm":
        p["ln1"], lg["ln1"] = norm_init(cfg.d_model)
        p["slstm"], lg["slstm"] = Xl.init_slstm(ks[0], cfg.xlstm)
        return p, lg
    else:
        raise ValueError(kind)
    p["ln2"], lg["ln2"] = norm_init(cfg.d_model)
    if kind.endswith("moe"):
        p["moe"], lg["moe"] = Moe.init_moe(ks[2], cfg.moe)
    else:
        if cfg.mlp_kind == "swiglu":
            p["mlp"], lg["mlp"] = L.init_swiglu(ks[2], cfg.d_model, cfg.d_ff)
        else:
            p["mlp"], lg["mlp"] = L.init_gelu_mlp(ks[2], cfg.d_model, cfg.d_ff)
    return p, lg


def _init_tree(key: jax.Array, cfg: ModelConfig):
    """(params, logical) as traced by :func:`init` and :func:`abstract_init`.
    Blocks stacked across periods: leaf[P, ...], built by ``vmap`` over the
    period keys rather than by stacking per-period copies."""
    params, logical = {}, {}
    key, k_emb, k_head = jax.random.split(key, 3)
    params["embed"] = (jax.random.normal(k_emb, (cfg.vocab, cfg.d_model))
                       * cfg.d_model ** -0.5).astype(cfg.param_dtype)
    logical["embed"] = ("vocab", "embed")
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(k_head, (cfg.d_model, cfg.vocab))
                             * cfg.d_model ** -0.5).astype(cfg.param_dtype)
        logical["lm_head"] = ("embed", "vocab")
    norm_init = L.init_rmsnorm if cfg.norm == "rmsnorm" else L.init_layernorm
    params["final_ln"], logical["final_ln"] = norm_init(cfg.d_model)

    period_keys = []
    for _ in range(cfg.n_periods):
        key, k = jax.random.split(key)
        period_keys.append(k)
    box = {}

    def init_period(k):
        per, per_lg = [], []
        for kind in cfg.block_pattern:
            k, kb = jax.random.split(k)
            bp, blg = _init_block(kb, kind, cfg)
            per.append(jax.tree.map(lambda x: x.astype(cfg.param_dtype), bp))
            per_lg.append(blg)
        box["logical"] = per_lg
        return per

    params["blocks"] = jax.vmap(init_period)(jnp.stack(period_keys))
    logical["blocks"] = jax.tree.map(lambda lgx: ("layers",) + lgx,
                                     box["logical"],
                                     is_leaf=lambda x: isinstance(x, tuple))
    if cfg.first_dense:
        params["lead"], logical["lead"] = [], []
        for _ in range(cfg.first_dense):
            key, kb = jax.random.split(key)
            bp, blg = _init_block(kb, "attn_mlp", cfg)
            params["lead"].append(
                jax.tree.map(lambda x: x.astype(cfg.param_dtype), bp))
            logical["lead"].append(blg)

    if cfg.encoder is not None:
        e = cfg.encoder
        enc_blocks, enc_lg = [], None
        ecfg = dataclasses.replace(
            cfg, n_layers=e.n_layers, d_model=e.d_model, n_heads=e.n_heads,
            n_kv_heads=e.n_heads, d_ff=e.d_ff, block_pattern=("attn_mlp",),
            mrope_sections=None)
        for pi in range(e.n_layers):
            key, kb = jax.random.split(key)
            bp, blg = _init_block(kb, "attn_mlp", ecfg)
            enc_blocks.append([bp])
            enc_lg = [blg]
        params["enc_blocks"] = jax.tree.map(
            lambda *xs: jnp.stack(xs).astype(cfg.param_dtype), *enc_blocks)
        logical["enc_blocks"] = jax.tree.map(lambda lgx: ("layers",) + lgx, enc_lg,
                                             is_leaf=lambda x: isinstance(x, tuple))
        params["enc_ln"], logical["enc_ln"] = norm_init(e.d_model)
        key, k_pos = jax.random.split(key)
        params["enc_pos"] = (jax.random.normal(k_pos, (e.n_frames, e.d_model))
                             * 0.01).astype(cfg.param_dtype)
        logical["enc_pos"] = ("seq", "embed_act")
    return params, logical


@partial(jax.jit, static_argnums=1)
def _init_params(key: jax.Array, cfg: ModelConfig):
    return _init_tree(key, cfg)[0]


def init(key: jax.Array, cfg: ModelConfig):
    """Returns (params, logical). Blocks stacked across periods: leaf[P, ...].

    One jitted program makes every leaf in ``cfg.param_dtype``: the device
    holds the finished parameters plus the temporaries of the leaf being
    made, never an fp32 copy of a bf16 model (llama3.2-3b fits one 16 GB
    chip).
    """
    return _init_params(key, cfg), abstract_init(cfg)[1]


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------

def _norm(cfg, p, x):
    return L.rmsnorm(p, x) if cfg.norm == "rmsnorm" else L.layernorm(p, x)


def _apply_block(p, kind: str, cfg: ModelConfig, x, positions, enc_out,
                 cache: dict | None, decode: bool):
    """Returns (x, new_cache, aux)."""
    aux = {}
    new_cache = cache
    if kind.startswith("attn"):
        h = _norm(cfg, p["ln1"], x)
        if cfg.mla is not None:
            if decode:
                raise ValueError(contiguous_unsupported_reason(cfg))
            a = L.mla_attention(p["attn"], h, cfg.mla, positions)
        elif decode:
            a, new_cache = L.attention_decode(p["attn"], h, cache["self"],
                                              cfg.attn_cfg(), positions)
            new_cache = {**cache, "self": new_cache}
        else:
            a = L.attention(p["attn"], h, cfg.attn_cfg(), positions)
        x = x + a
        if "cross" in kind:
            h = _norm(cfg, p["lnx"], x)
            xcfg = L.AttnConfig(cfg.d_model, cfg.n_heads, cfg.n_heads, causal=False)
            # cross-attention: q from decoder, kv from encoder output
            B, Sq, _ = h.shape
            q = L.dense(p["xattn"]["q"], h).reshape(B, Sq, cfg.n_heads, xcfg.dh)
            k = L.dense(p["xattn"]["k"], enc_out).reshape(B, -1, cfg.n_heads, xcfg.dh)
            v = L.dense(p["xattn"]["v"], enc_out).reshape(B, -1, cfg.n_heads, xcfg.dh)
            o = L.flash_attention(q, k, v, causal=False, block=512)
            x = x + L.dense(p["xattn"]["o"], o.reshape(B, Sq, -1))
    elif kind.startswith("mamba"):
        h = _norm(cfg, p["ln1"], x)
        m_state = cache["mamba"] if decode else None
        m, m_state = Mb.mamba(p["mamba"], h, cfg.mamba, m_state)
        if decode:
            new_cache = {**cache, "mamba": m_state}
        x = x + m
    elif kind == "mlstm":
        h = _norm(cfg, p["ln1"], x)
        m, st = Xl.mlstm(p["mlstm"], h, cfg.xlstm, cache["mlstm"] if decode else None)
        if decode:
            new_cache = {**cache, "mlstm": st}
        return x + m, new_cache, aux
    elif kind == "slstm":
        h = _norm(cfg, p["ln1"], x)
        m, st = Xl.slstm(p["slstm"], h, cfg.xlstm, cache["slstm"] if decode else None)
        if decode:
            new_cache = {**cache, "slstm": st}
        return x + m, new_cache, aux
    # FFN half
    h = _norm(cfg, p["ln2"], x)
    if kind.endswith("moe"):
        m, aux = Moe.moe(p["moe"], h, cfg.moe)
    elif cfg.mlp_kind == "swiglu":
        m = L.swiglu(p["mlp"], h)
    else:
        m = L.gelu_mlp(p["mlp"], h)
    return x + m, new_cache, aux


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, tokens, vision_embeds=None):
    emb = jnp.take(params["embed"].astype(cfg.activ_dtype), tokens, axis=0)
    if cfg.vision_patches and vision_embeds is not None:
        P = cfg.vision_patches
        emb = jnp.concatenate([vision_embeds.astype(cfg.activ_dtype),
                               emb[:, P:]], axis=1)
    return shard(emb, "batch", "seq", "embed_act")


def _encoder_forward(params, cfg: ModelConfig, frames):
    e = cfg.encoder
    x = frames.astype(cfg.activ_dtype) + params["enc_pos"].astype(cfg.activ_dtype)
    ecfg = dataclasses.replace(
        cfg, d_model=e.d_model, n_heads=e.n_heads, n_kv_heads=e.n_heads,
        d_ff=e.d_ff, mrope_sections=None)

    def body(x, bp):
        x, _, _ = _apply_block(bp[0], "attn_mlp", dataclasses.replace(
            ecfg, block_pattern=("attn_mlp",)), x, None, None, None, False)
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return _norm(cfg, params["enc_ln"], x)


def forward(params, cfg: ModelConfig, tokens, positions=None, vision_embeds=None,
            encoder_frames=None):
    """tokens [B, S] -> logits [B, S, vocab] (fp32)."""
    B, S = tokens.shape
    x = _embed(params, cfg, tokens, vision_embeds)
    if positions is None and cfg.mrope_sections is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    enc_out = _encoder_forward(params, cfg, encoder_frames) \
        if cfg.encoder is not None else None
    aux_acc = {"load_balance": 0.0, "router_z": 0.0, "dropped_frac": 0.0}

    for lead in params.get("lead", ()):
        x, _, _ = _apply_block(lead, "attn_mlp", cfg, x, positions, None,
                               None, False)

    def period_body(x, period_params):
        auxes = {}
        for bi, kind in enumerate(cfg.block_pattern):
            x, _, aux = _apply_block(
                jax.tree.map(lambda t: t, period_params[bi]), kind, cfg, x,
                positions, enc_out, None, False)
            for k_, v_ in aux.items():
                auxes[k_] = auxes.get(k_, 0.0) + v_
        # Megatron-SP: the remat-saved period boundary is sharded over `model`
        # along the sequence, cutting saved-activation memory by the TP degree.
        if x.shape[1] > 1:
            x = shard(x, "batch", "seq_res", "embed_act")
        return x, auxes

    body = period_body
    if cfg.remat:
        policy = None if cfg.remat_policy == "full" else \
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        body = jax.checkpoint(period_body, policy=policy)
    x, auxes = jax.lax.scan(body, x, params["blocks"])
    if auxes:
        for k_ in aux_acc:
            if k_ in auxes:
                aux_acc[k_] = jnp.sum(auxes[k_])
    x = _norm(cfg, params["final_ln"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(cfg.activ_dtype)
    return shard(logits.astype(jnp.float32), "batch", "seq", "vocab"), aux_acc


def loss_fn(params, cfg: ModelConfig, batch: dict):
    """Next-token CE. batch: tokens [B, S], plus arch-specific extras."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          positions=batch.get("positions"),
                          vision_embeds=batch.get("vision_embeds"),
                          encoder_frames=batch.get("encoder_frames"))
    targets = batch["tokens"][:, 1:]
    lg = logits[:, :-1]
    # CE without gathering along the vocab-sharded axis: take_along_axis on a
    # sharded dim makes GSPMD replicate the full [B,S,V] logits; the one-hot
    # contraction keeps everything vocab-sharded + one small all-reduce.
    lse = jax.nn.logsumexp(lg, axis=-1)
    onehot = jax.nn.one_hot(targets, cfg.vocab, dtype=lg.dtype)
    onehot = shard(onehot, "batch", "seq", "vocab")
    target_logit = jnp.einsum("bsv,bsv->bs", lg, onehot)
    nll = lse - target_logit
    mask = batch.get("loss_mask")
    mask = mask[:, 1:] if mask is not None else jnp.ones_like(nll)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + 0.01 * aux["load_balance"] + aux["router_z"]
    return total, {"ce": loss, **{k: v for k, v in aux.items()}}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def contiguous_unsupported_reason(cfg: ModelConfig) -> str | None:
    """None when the contiguous cache can serve ``cfg``, else the reason."""
    if cfg.mla is not None:
        return ("latent attention (MLA) is served from the paged latent "
                "pool only (LMEngine(paged=PagedConfig(...))); the "
                "contiguous cache holds per-head K/V")
    return None


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked per-period caches mirroring the block pattern."""
    reason = contiguous_unsupported_reason(cfg)
    if reason is not None:
        raise ValueError(reason)
    if cfg.kv_cache_dtype == "int8":
        dtype = jnp.int8
    per = []
    for kind in cfg.block_pattern:
        if kind.startswith("attn"):
            c = {"self": L.init_kv_cache(batch, max_len, cfg.attn_cfg(), dtype)}
        elif kind.startswith("mamba"):
            c = {"mamba": Mb.init_mamba_state(batch, cfg.mamba, dtype)}
        elif kind == "mlstm":
            c = {"mlstm": Xl.init_mlstm_state(batch, cfg.xlstm)}
        else:
            c = {"slstm": Xl.init_slstm_state(batch, cfg.xlstm)}
        per.append(c)
    # stack across periods
    stacked = jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf[None], (cfg.n_periods,) + leaf.shape).copy()
        if cfg.n_periods > 1 else leaf[None],
        per)
    return stacked


def cache_logical(cfg: ModelConfig):
    """Logical axes for the cache pytree (for dry-run shardings)."""
    per = []
    for kind in cfg.block_pattern:
        if kind.startswith("attn"):
            kv = {"k": ("layers", "batch", "seq", "kv_heads", None),
                  "v": ("layers", "batch", "seq", "kv_heads", None),
                  "len": ("layers", "batch")}
            if cfg.kv_cache_dtype == "int8":
                kv["k_scale"] = ("layers", "batch", "seq", "kv_heads", None)
                kv["v_scale"] = ("layers", "batch", "seq", "kv_heads", None)
            per.append({"self": kv})
        elif kind.startswith("mamba"):
            per.append({"mamba": {"conv": ("layers", "batch", None, "mlp"),
                                  "ssm": ("layers", "batch", "mlp", None)}})
        elif kind == "mlstm":
            per.append({"mlstm": {"C": ("layers", "batch", "heads", None, None),
                                  "n": ("layers", "batch", "heads", None),
                                  "m": ("layers", "batch", "heads")}})
        else:
            per.append({"slstm": {k: ("layers", "batch", "mlp") for k in
                                  ("c", "n", "m", "h")}})
    return per


def decode_step(params, cfg: ModelConfig, cache, tokens, positions=None,
                enc_out=None):
    """One decode step. tokens [B, 1] -> (logits [B, 1, vocab], new_cache)."""
    x = _embed(params, cfg, tokens)
    if positions is None and cfg.mrope_sections is None:
        # position = current cache length (uniform across rows by construction)
        lens = _first_len(cache, cfg)
        positions = jnp.broadcast_to(lens[:, None], tokens.shape)

    def period_body(x, scanned):
        period_params, period_cache = scanned
        new_caches = []
        for bi, kind in enumerate(cfg.block_pattern):
            x, nc, _ = _apply_block(period_params[bi], kind, cfg, x, positions,
                                    enc_out, period_cache[bi], True)
            new_caches.append(nc)
        return x, new_caches

    # scan over periods, threading cache through as scanned input+output
    x, new_cache = _scan_with_cache(period_body, x, params["blocks"], cache, cfg)
    x = _norm(cfg, params["final_ln"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(cfg.activ_dtype)).astype(jnp.float32)
    return logits, new_cache


def _first_len(cache, cfg: ModelConfig):
    for bi, kind in enumerate(cfg.block_pattern):
        if kind.startswith("attn"):
            return cache[bi]["self"]["len"][0]  # [B] of period 0
    return jnp.zeros((1,), jnp.int32)  # pure-SSM stacks: rope positions unused


def _scan_with_cache(body, x, blocks, cache, cfg: ModelConfig):
    def f(carry, scanned):
        x = carry
        pp, pc = scanned
        x, new_pc = body(x, (pp, pc))
        return x, new_pc

    x, new_cache = jax.lax.scan(f, x, (blocks, cache))
    return x, new_cache


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def abstract_init(cfg: ModelConfig):
    """(param ShapeDtypeStructs, logical axes) with zero allocation.

    The logical tree is static metadata built alongside tracing, so one
    eval_shape pass yields both — this is what lets the 398B config's
    dry-run start instantly.
    """
    box = {}

    def f(key):
        params, box["logical"] = _init_tree(key, cfg)
        return params

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, box["logical"]


def count_params_cfg(cfg: ModelConfig) -> tuple:
    """(total params, active-per-token params) from shapes alone.

    Active excludes the (E - top_k)/E fraction of expert weights (MoE) —
    the N_active of the MODEL_FLOPS = 6*N_active*D roofline row.
    """
    shapes, _ = abstract_init(cfg)
    total = 0
    moe_total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        total += leaf.size
        keys = [getattr(k, "key", None) for k in path]
        if "moe" in keys and "shared" not in keys:
            name = getattr(path[-1], "key", "")
            if name in ("gate", "up", "down"):
                moe_total += leaf.size
    active = total - moe_total
    if cfg.moe is not None and moe_total:
        active += moe_total * cfg.moe.top_k / cfg.moe.num_experts
    return int(total), int(active)
