"""Public dispatch for paged flash-decode attention (kernel vs reference).

The serving stack (``repro.lm`` model functions -> ``lm/kv.PagedKV``) calls
:func:`flash_decode` with a KV *pool* dict and a block table; ``use_flash``
selects the Pallas online-softmax kernel or the dense gathered reference,
``interpret=None`` resolves to interpret mode off-TPU (the CPU CI path) —
the same convention as the resonator ``FusedConfig``.
"""
from __future__ import annotations

from repro.kernels import resolve_interpret
from repro.kernels.flash_decode import kernel as _k
from repro.kernels.flash_decode import ref as _ref


def flash_decode(q, pool: dict, table, kv_lens, *, use_flash: bool = True,
                 interpret: bool | None = None, v_width: int | None = None,
                 layer=None):
    """Decode attention over a paged KV pool.

    q: [B, G, rep, dh] pre-scaled f32; pool: {"k", "v"} (+ "k_scale",
    "v_scale" when int8) with leaves [NBP, bs, G, dh]; table [B, W] int32;
    kv_lens [B] int32 valid-position counts.  Returns [B, G, rep, dh] f32.
    A latent pool {"lat": [NBP, bs, Dk]} takes q [B, H, Dk] and returns
    [B, H, v_width]: each entry's first ``v_width`` lanes are its value.
    With ``layer`` every leaf has a leading layer axis, read at ``layer``.
    """
    if "lat" in pool:
        k, v, ks, vs = pool["lat"], None, None, None
    else:
        k, v = pool["k"], pool["v"]
        ks, vs = pool.get("k_scale"), pool.get("v_scale")
    if use_flash:
        return _k.flash_decode(q, k, v, table, kv_lens, k_scale=ks,
                               v_scale=vs, v_width=v_width, layer=layer,
                               interpret=resolve_interpret(interpret))
    if layer is not None:
        k, v, ks, vs = (None if a is None else a[layer]
                        for a in (k, v, ks, vs))
    return _ref.flash_decode_ref(q, k, v, table, kv_lens, k_scale=ks,
                                 v_scale=vs, v_width=v_width)


flash_decode_ref = _ref.flash_decode_ref
