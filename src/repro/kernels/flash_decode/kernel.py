"""Online-softmax paged flash-decode Pallas kernel.

One decode step's attention for a batch of slots whose KV lives in a shared
block pool, addressed through per-slot block tables (see ref.py for the
layout contract).  The grid is ``(B, W)``: program ``(b, i)`` loads row
``b``'s i-th logical KV block straight from the pool — the block table
rides in as a scalar-prefetch operand, so the BlockSpec index_map
``tab[b, i]`` turns the gather into the pipeline's own HBM->VMEM copy; no
materialised [B, W*bs, ...] gather ever exists.

Per tile the kernel keeps the flash-attention running statistics in VMEM
scratch (persistent across the innermost grid axis): running max ``m``,
running denominator ``l``, unnormalised accumulator ``acc``, rescaled by
``exp(m_old - m_new)`` per tile.  The tail block is handled by masking
positions ``>= kv_lens[b]`` to -1e30 (same sentinel as the dense paths);
whole blocks past the live window are skipped under ``@pl.when`` — their
HBM traffic is still issued by the pipeline (the copy is unconditional)
but no FLOPs run, and table padding keeps the loads in-range.  With an
int8 pool the per-(token, head) dequant scales ride in through the same
block table and the dequant fuses into the tile load.

Numerics: f32 throughout (matching attention_decode's f32 softmax).  The
online rescaling reassociates the softmax sum across tiles, so outputs are
equal to the dense reference only within a small f32 tolerance (~1e-5
relative; documented in DESIGN.md) — the serving-level contract (greedy
token streams bit-equal across block sizes) is asserted in
tests/test_paging.py on top of this.

Latent (MLA) pools take the same grid and block table: with no separate
value pool, one ``[bs, Dk]`` tile per grid step is the key (all ``Dk``
lanes) and, through its first ``v_width`` lanes, the value, for every
query head at once.  That call is named ``mla_decode`` so a
profiler trace can find it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _body(table_ref, lens_ref, *rest, block_size: int, quantized: bool,
          v_width: int | None, layered: bool):
    latent = v_width is not None
    if layered:  # the layer index only steers the index maps
        rest = rest[1:]
    q_ref, k_ref, *rest = rest
    if latent:
        o_ref, m_ref, l_ref, acc_ref = rest
    elif quantized:
        v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        v_ref, o_ref, m_ref, l_ref, acc_ref = rest
    b, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid_n = lens_ref[b]
    start = i * block_size

    @pl.when(start < valid_n)
    def _tile():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        if latent:
            # q [H, Dk]; the tile [bs, Dk] is K, its first lanes V
            v = k[:, :v_width]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            pv_dims = (((1,), (0,)), ((), ()))
        else:
            # q [G, rep, dh]; k, v tiles [bs, G, dh]
            v = v_ref[0].astype(jnp.float32)
            if quantized:
                k = k * ks_ref[0]              # [bs, G, 1] broadcast
                v = v * vs_ref[0]
            # scores: batch over G, contract dh -> [G, rep, bs]
            s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (1,))),
                                    preferred_element_type=jnp.float32)
            pv_dims = (((2,), (0,)), ((0,), (1,)))
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
        s = jnp.where(pos < valid_n, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=latent))
        p = jnp.exp(s - (m_new if latent else m_new[..., None]))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=latent)
        pv = jax.lax.dot_general(p, v, pv_dims,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * (corr if latent else corr[..., None]) \
            + pv
        m_ref[...] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / (l if latent else l[..., None]))[None]


def flash_decode(q, k_pool, v_pool, table, kv_lens, *, k_scale=None,
                 v_scale=None, v_width: int | None = None, layer=None,
                 interpret: bool = False):
    """Paged online-softmax decode attention (see ref.py for shapes).

    ``v_pool=None`` reads a latent pool ``[NBP, bs, Dk]`` with q ``[B, H,
    Dk]``: the first ``v_width`` lanes of each key are its value, and the
    result is ``[B, H, v_width]``.  With ``layer`` (a scalar) every pool
    carries a leading layer axis and the kernel reads that layer's blocks
    in place.  Exactly one ``pallas_call`` per invocation — the
    jaxpr-checked serving contract (tests/test_paging.py).
    """
    latent = v_pool is None
    layered = layer is not None
    W = table.shape[1]
    bs = int(k_pool.shape[-2] if latent else k_pool.shape[-3])
    quantized = k_pool.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 KV pool requires k_scale/v_scale pools")
    if latent and v_width is None:
        raise ValueError("a latent pool (v_pool=None) needs v_width")

    def _block(b, i, ln):
        # Clamp dead tiles (past the row's live window) to the LAST live
        # block: consecutive grid steps with an unchanged block index make
        # the pipeline skip the HBM->VMEM copy, so a row's KV traffic is
        # ceil(len/bs) block gathers — the structural win the cost model
        # prices — while @pl.when skips the compute.
        live = jnp.maximum((ln[b] + bs - 1) // bs, 1)
        return jnp.minimum(i, live - 1)

    lead = (pl.Squeezed(),) if layered else ()

    def _pool(*block):
        """Block spec of a pool leaf: row b's i-th live block (of the
        prefetched layer, when layered)."""
        def index(b, i, tab, ln, *lay):
            at = (lay[0][0],) if layered else ()
            return (*at, tab[b, _block(b, i, ln)], *(0,) * len(block))
        return pl.BlockSpec(lead + (1,) + block, index)

    if latent:
        B, H, Dk = q.shape
        pool_spec = _pool(bs, Dk)
        row = lambda b, i, *_: (b, 0, 0)  # noqa: E731
        in_specs = [pl.BlockSpec((1, H, Dk), row), pool_spec]
        operands = [q.astype(jnp.float32), k_pool]
        out_block, out_shape = (1, H, v_width), (B, H, v_width)
        stats = (H, 1)
        name = "mla_decode"
    else:
        B, G, rep, dh = q.shape
        pool_spec = _pool(bs, G, dh)
        row = lambda b, i, *_: (b, 0, 0, 0)  # noqa: E731
        in_specs = [pl.BlockSpec((1, G, rep, dh), row), pool_spec, pool_spec]
        operands = [q.astype(jnp.float32), k_pool, v_pool]
        if quantized:
            scale_spec = _pool(bs, G, 1)
            in_specs += [scale_spec, scale_spec]
            operands += [k_scale, v_scale]
        out_block, out_shape = (1, G, rep, dh), (B, G, rep, dh)
        stats = (G, rep)
        name = None

    prefetch = [table, kv_lens]
    if layered:
        prefetch.append(jnp.asarray(layer, jnp.int32).reshape(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, W),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, row),
        scratch_shapes=[pltpu.VMEM(stats, jnp.float32),
                        pltpu.VMEM(stats, jnp.float32),
                        pltpu.VMEM(out_shape[1:], jnp.float32)],
    )
    return pl.pallas_call(
        partial(_body, block_size=bs, quantized=quantized,
                v_width=v_width if latent else None, layered=layered),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
        name=name,
    )(*prefetch, *operands)
