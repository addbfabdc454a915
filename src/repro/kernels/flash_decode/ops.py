"""Public dispatch for paged flash-decode attention (kernel vs reference).

The serving stack (``repro.lm`` model functions -> ``launch/serve``) calls
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
                 interpret: bool | None = None):
    """Decode attention over a paged KV pool.

    q: [B, G, rep, dh] pre-scaled f32; pool: {"k", "v"} (+ "k_scale",
    "v_scale" when int8) with leaves [NBP, bs, G, dh]; table [B, W] int32;
    kv_lens [B] int32 valid-position counts.  Returns [B, G, rep, dh] f32.
    """
    ks, vs = pool.get("k_scale"), pool.get("v_scale")
    if use_flash:
        return _k.flash_decode(q, pool["k"], pool["v"], table, kv_lens,
                               k_scale=ks, v_scale=vs,
                               interpret=resolve_interpret(interpret))
    return _ref.flash_decode_ref(q, pool["k"], pool["v"], table, kv_lens,
                                 k_scale=ks, v_scale=vs)


flash_decode_ref = _ref.flash_decode_ref
