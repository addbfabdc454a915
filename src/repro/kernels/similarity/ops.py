"""Public wrapper: quantised codebook similarity with backend dispatch."""
from __future__ import annotations

import jax

from repro.core.quantization import QTensor
from repro.kernels import resolve_interpret
from repro.kernels.similarity import kernel as _k
from repro.kernels.similarity import ref as _ref


def codebook_scores(q: jax.Array, codebook: QTensor) -> jax.Array:
    """Scores [..., M] of queries [..., D] against an int8 codebook [M, D]."""
    lead = q.shape[:-1]
    q2 = q.reshape(-1, q.shape[-1])
    out = _k.similarity_int8(
        q2, codebook.values, codebook.scale,
        interpret=resolve_interpret(),
    )
    return out.reshape(*lead, -1)


similarity_int8_ref = _ref.similarity_int8_ref
