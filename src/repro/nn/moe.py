"""Mixture-of-Experts layer: top-k router with sort-based capacity dispatch.

MaxText-style dropping MoE: tokens are sorted by assigned expert, each expert
processes a fixed-capacity slice (static shapes — required for jit/pjit), and
overflow tokens fall back to the residual path.  Experts are sharded over the
`model` mesh axis (EP); with tokens sharded over `data`, GSPMD inserts the
all-to-all at the dispatch/combine boundaries.

granite-moe (40e top-8), dbrx (16e top-4) and jamba (16e top-2) all run
through this layer.

DeepSeekMoE (deepseek-v2-lite) runs the drop-free expert share
(:func:`moe_share`, selected by ``MoEConfig.held``): the router scores all
``num_experts``, and the layer computes only the experts this chip holds,
each over exactly the tokens routed to it, plus the shared experts for
every token.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.nn.common import shard
from repro.nn import layers as L


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int  # per-expert hidden
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    # The drop-free expert share: ``held`` experts, ids ``held_from ..
    # held_from + held - 1`` of ``num_experts``, live here (None: the
    # capacity path over all of them).  ``n_shared`` experts of width
    # ``d_ff`` see every token; the top-k weights are renormalised only
    # with ``norm_topk`` and then scaled by ``routed_scale``.
    held: int | None = None
    held_from: int = 0
    n_shared: int = 0
    norm_topk: bool = True
    routed_scale: float = 1.0

    @property
    def n_local(self) -> int:
        """Experts whose weights this layer holds."""
        return self.num_experts if self.held is None else self.held


def init_moe(key, cfg: MoEConfig):
    ks = jax.random.split(key, 5)
    scale_in = (1.0 / cfg.d_model) ** 0.5
    scale_out = (1.0 / cfg.d_ff) ** 0.5
    E = cfg.n_local
    p = {
        "router": (jax.random.normal(ks[0], (cfg.d_model, cfg.num_experts))
                   * scale_in).astype(jnp.float32),
        "gate": (jax.random.normal(ks[1], (E, cfg.d_model, cfg.d_ff))
                 * scale_in).astype(jnp.float32),
        "up": (jax.random.normal(ks[2], (E, cfg.d_model, cfg.d_ff))
               * scale_in).astype(jnp.float32),
        "down": (jax.random.normal(ks[3], (E, cfg.d_ff, cfg.d_model))
                 * scale_out).astype(jnp.float32),
    }
    lg = {
        "router": ("embed", None if cfg.held is not None else "experts"),
        "gate": ("experts", "embed", "mlp"),
        "up": ("experts", "embed", "mlp"),
        "down": ("experts", "mlp", "embed"),
    }
    if cfg.n_shared:
        p["shared"], lg["shared"] = L.init_swiglu(
            ks[4], cfg.d_model, cfg.n_shared * cfg.d_ff)
    return p, lg


def _route_local(x, top_e, top_p, *, E: int, K: int, cap: int, fold: int = 1):
    """Pure-local token->slot permutation (runs per data shard).

    x: [B, S, d]; top_e/top_p: [B, S, K].  Returns disp [B/fold, E, cap, d]
    plus the metadata combine needs.  Every op keeps the leading batch dim.
    `fold` groups rows into one routing pool — at decode (S=1) a single row
    would otherwise dispatch E slots for K active experts, wasting E/(K*cf)
    of the expert matmul (the granite decode cell's 23% useful fraction).
    """
    if fold > 1:
        B0, S0, d0 = x.shape
        x = x.reshape(B0 // fold, fold * S0, d0)
        top_e = top_e.reshape(B0 // fold, fold * S0, K)
        top_p = top_p.reshape(B0 // fold, fold * S0, K)
    B, S, d = x.shape
    Tk = S * K
    flat_e = top_e.reshape(B, Tk)
    flat_w = top_p.reshape(B, Tk).astype(x.dtype)
    tok_of = jnp.broadcast_to(jnp.repeat(jnp.arange(S), K)[None], (B, Tk))
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    se = jnp.take_along_axis(flat_e, order, axis=-1)  # [B, Tk]
    sw = jnp.take_along_axis(flat_w, order, axis=-1)
    st = jnp.take_along_axis(tok_of, order, axis=-1)
    onehot = jax.nn.one_hot(se, E, dtype=jnp.int32)  # [B, Tk, E]
    counts = jnp.sum(onehot, axis=1)  # [B, E]
    starts = jnp.cumsum(counts, axis=-1) - counts
    rank = jnp.arange(Tk)[None] - jnp.take_along_axis(starts, se, axis=-1)
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, E * cap)  # overflow -> scratch row
    # NOTE (§Perf item 8): a gather-form dispatch (inverse permutation) was
    # tried to kill the scatter's per-element u32 index temporaries — it
    # bought only ~3% temp memory at jamba scale and trips an XLA SPMD
    # partitioner CHECK on padded (uneven-expert) shardings, so the batched
    # scatter stands.
    brow = jnp.arange(B)[:, None]
    vals = jnp.where(keep[..., None],
                     jnp.take_along_axis(x, st[..., None], axis=1), 0.0)
    disp = jnp.zeros((B, E * cap + 1, d), x.dtype).at[brow, slot].set(vals)
    return disp[:, : E * cap].reshape(B, E, cap, d), st, slot, sw, keep


def _combine_local(out, st, slot, sw, keep, *, S: int, fold: int = 1):
    """Scatter-add expert outputs back to token positions (per data shard)."""
    B, EC, d = out.shape
    contrib = jnp.where(
        keep[..., None],
        jnp.take_along_axis(out, jnp.clip(slot, 0, EC - 1)[..., None], axis=1)
        * sw[..., None], 0.0)
    y = jnp.zeros((B, S * fold, d), out.dtype).at[
        jnp.arange(B)[:, None], st].add(contrib)
    return y.reshape(B * fold, S, d) if fold > 1 else y


def _batch_manual(fn, n_out: int):
    """shard_map `fn` over the batch mesh axes when a mesh is active.

    GSPMD cannot partition the general gather/scatter chains of token
    routing and replicates them (100s of GiB at jamba scale); running them
    *manually* per data shard makes every permutation local.  Expert weights
    never enter these functions, so `model` stays an auto axis.
    """
    from repro.nn.common import current_mesh
    v = current_mesh()
    if v is None:
        return fn
    mesh, rules = v
    b_rule = rules.get("batch")
    axes = tuple(a for a in ((b_rule,) if isinstance(b_rule, str) else (b_rule or ()))
                 if a in mesh.axis_names)
    if not axes:
        return fn
    from jax.sharding import PartitionSpec as P

    spec = P(axes if len(axes) > 1 else axes[0])
    return jax.shard_map(fn, mesh=mesh, in_specs=spec,
                         out_specs=(spec,) * n_out if n_out > 1 else spec,
                         axis_names=set(axes), check_vma=False)


def route(p, x: jax.Array, cfg: MoEConfig) -> tuple:
    """Softmax router over all ``num_experts`` in f32, greedy top-k:
    (probs [.., E], top_p [.., K] weights as :func:`moe_share` applies
    them, top_e [.., K] expert ids)."""
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
    return probs, top_p * cfg.routed_scale, top_e


def moe_share(p, x: jax.Array, cfg: MoEConfig) -> tuple:
    """The drop-free expert share: x [B, S, d] -> (y, aux).

    Each of the ``cfg.held`` experts here computes exactly the tokens
    routed to it (``ragged_dot`` over the token-expert picks sorted by
    held expert; picks of experts held elsewhere sort last and take no
    part), weighted by its router weight; the shared experts add their
    MLP for every token.  A token that picked no held expert gets the
    shared part only: what the absent experts would add is computed by
    the chips that hold them.  ``aux["held_picks"]`` [B, S] counts each
    token's picks that landed here.
    """
    B, S, d = x.shape
    T, K, E = B * S, cfg.top_k, cfg.held
    xt = x.reshape(T, d)
    probs, top_p, top_e = route(p, xt, cfg)
    local = top_e - cfg.held_from
    here = (local >= 0) & (local < E)
    group = jnp.where(here, local, E).reshape(T * K)
    order = jnp.argsort(group, stable=True)
    tok = order // K
    sizes = jnp.sum(jax.nn.one_hot(group, E, dtype=jnp.int32), axis=0)
    xs = jnp.take(xt, tok, axis=0)
    dt = x.dtype
    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["gate"].astype(dt), sizes)) \
        * jax.lax.ragged_dot(xs, p["up"].astype(dt), sizes)
    out = jax.lax.ragged_dot(h, p["down"].astype(dt), sizes)
    w = jnp.where(here, top_p, 0.0).reshape(T * K)[order]
    contrib = jnp.where((w > 0)[:, None], out.astype(jnp.float32) * w[:, None],
                        0.0)
    y = jnp.zeros((T, d), jnp.float32).at[tok].add(contrib)
    if cfg.n_shared:
        y = y + L.swiglu(p["shared"], xt[None])[0].astype(jnp.float32)
    me = jnp.mean(jax.nn.one_hot(top_e[..., 0], cfg.num_experts), axis=0)
    aux = {"load_balance": cfg.num_experts * jnp.sum(
               me * jnp.mean(probs, axis=0)),
           "router_z": jnp.float32(0.0), "dropped_frac": jnp.float32(0.0),
           "held_picks": jnp.sum(here, axis=-1).reshape(B, S)}
    return y.astype(dt).reshape(B, S, d), aux


def moe(p, x: jax.Array, cfg: MoEConfig) -> tuple:
    """x: [B, S, d] -> (y [B, S, d], aux_losses dict).

    Routing is PER BATCH ROW and shard_mapped over the data axes (see
    _batch_manual); capacity is per-row: cap = S * k * cf / E.  The expert
    einsums stay under GSPMD with experts sharded over `model` (EP).
    A config with ``held`` set runs :func:`moe_share` instead.
    """
    if cfg.held is not None:
        return moe_share(p, x, cfg)
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    B_out = B  # output batch (fold-restored by _combine_local)
    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)  # [B, S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)  # [B, S, K]
    top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-9)

    # Decode (S==1): pool each data shard's rows into ONE routing group so
    # capacity is sized for B_loc*K assignments instead of E slots per row.
    fold = 1
    if S == 1 and B > 1:
        from repro.nn.common import current_mesh
        v = current_mesh()
        dp = 1
        if v is not None:
            mesh_, rules_ = v
            b_rule = rules_.get("batch")
            sizes = dict(zip(mesh_.axis_names, mesh_.devices.shape))
            dp = 1
            for a in ((b_rule,) if isinstance(b_rule, str) else (b_rule or ())):
                dp *= sizes.get(a, 1)
        if B % max(dp, 1) == 0:
            fold = max(1, B // max(dp, 1))
    cap = int(max(1, round(S * fold * K * cfg.capacity_factor / E)))
    route = _batch_manual(
        partial(_route_local, E=E, K=K, cap=cap, fold=fold), n_out=5)
    disp, st, slot, sw, keep = route(x, top_e, top_p)
    disp = shard(disp, "batch", "experts", None, None)
    # expert FFN: batched matmul, experts sharded over `model` (EP)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", disp, p["gate"].astype(x.dtype))) \
        * jnp.einsum("becd,edf->becf", disp, p["up"].astype(x.dtype))
    h = shard(h, "batch", "experts", None, "mlp")
    out = jnp.einsum("becf,efd->becd", h, p["down"].astype(x.dtype))
    out = shard(out, "batch", "experts", None, None).reshape(B // fold, E * cap, d)
    # combine: scatter-add back to token positions, manual over data shards
    combine = _batch_manual(partial(_combine_local, S=S, fold=fold), n_out=1)
    y = combine(out, st, slot, sw, keep)
    # aux losses: load balance (Switch) + router z-loss
    me = jnp.mean(jax.nn.one_hot(top_e[..., 0], E), axis=(0, 1))
    ce = jnp.mean(probs, axis=(0, 1))
    aux = {
        "load_balance": E * jnp.sum(me * ce),
        "router_z": cfg.router_z_loss * jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return shard(y, "batch", "seq", "embed_act"), aux
