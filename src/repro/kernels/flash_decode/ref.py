"""Reference paged flash-decode: dense gathered-window attention in plain jnp.

Mirrors the math of :func:`repro.nn.layers.attention_decode` — f32 scores
over the full masked window, one `jax.nn.softmax` — but reads KV through a
block table into a shared pool instead of a contiguous per-row cache.  The
Pallas kernel (kernel.py) must match this within the documented tolerance;
this is also the CPU fallback when the fused path is disabled.

Shared layout contract (ref + kernel):

  * q:        [B, G, rep, dh] f32, PRE-scaled by dh**-0.5 by the caller;
  * k/v pool: [NBP, bs, G, dh] — NBP physical blocks of bs token positions
    (the last physical block is conventionally the trash block writes to
    dead rows scatter into; the table never has to point at it for live
    positions);
  * table:    [B, W] int32 — per-row logical->physical block ids, padded
    with any in-range id past the row's live window (masking makes padded
    blocks unreachable);
  * kv_lens:  [B] int32 — number of VALID kv positions per row (a decode
    step that just wrote position `len` passes `len + 1`);
  * k_scale/v_scale: [NBP, bs, G, 1] f32 when the pool is int8.

Returns [B, G, rep, dh] f32 (un-projected per-head context).

A latent (MLA) pool has no value pool (``v_pool=None``): k_pool is
[NBP, bs, Dk], q is [B, H, Dk] (every head reads the same entries), the
value of a position is the first ``v_width`` lanes of its key, and the
result is [B, H, v_width].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_decode_ref(q, k_pool, v_pool, table, kv_lens,
                     k_scale=None, v_scale=None, v_width=None):
    W = table.shape[1]
    k = k_pool[table].astype(jnp.float32)  # [B, W, bs, G, dh] | [B, W, bs, Dk]
    if v_pool is None:
        B, bs = q.shape[0], k_pool.shape[1]
        k = k.reshape(B, W * bs, k.shape[-1])
        s = jnp.einsum("bhd,bkd->bhk", q.astype(jnp.float32), k)
        pos = jnp.arange(W * bs)
        s = jnp.where(pos[None, None, :] < kv_lens[:, None, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhk,bkd->bhd", w, k[..., :v_width])
    B, G, rep, dh = q.shape
    bs = k_pool.shape[1]
    v = v_pool[table].astype(jnp.float32)
    if k_scale is not None:
        k = k * k_scale[table]
        v = v * v_scale[table]
    k = k.reshape(B, W * bs, G, dh)
    v = v.reshape(B, W * bs, G, dh)
    s = jnp.einsum("bgrd,bkgd->bgrk", q.astype(jnp.float32), k)
    pos = jnp.arange(W * bs)
    s = jnp.where(pos[None, None, None, :] < kv_lens[:, None, None, None],
                  s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgrk,bkgd->bgrd", w, v)
