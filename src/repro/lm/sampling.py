"""Per-request sampling specs for LM serving.

:class:`SamplingSpec` is what ``LMEngine.submit(..., sampling=...)``
accepts: temperature / top-k with a per-request seed.  The PRNG key for
each emitted token is ``fold_in(PRNGKey(seed), absolute_position)`` — a
pure function of the request's own seed and the token's position, NOT of
wall-clock or engine state — so a replayed request (fault recovery, resize
re-queue) regenerates bit-equal tokens, the same warm-handoff contract
greedy decode gets for free.

Validation lives in ``__post_init__`` so ``temperature=0`` (a
divide-by-zero inside ``categorical`` — zero temperature IS greedy, ask for
that) dies with a clear message at construction instead of an opaque jax
error at decode time; there is no key to forget, keys derive from
``seed``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingSpec:
    temperature: float = 1.0
    top_k: int | None = None
    seed: int = 0

    def __post_init__(self):
        if not self.temperature > 0:
            raise ValueError(
                f"temperature must be > 0, got {self.temperature} — "
                "temperature=0 is greedy argmax; pass sampling=None (the "
                "greedy default) instead of dividing logits by zero")
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")


def sample_token(logits, spec: SamplingSpec, position: int) -> int:
    """Sample one token id from [V] logits at an absolute sequence
    position.  Deterministic in (spec.seed, position) — see module doc."""
    key = jax.random.fold_in(jax.random.PRNGKey(spec.seed), position)
    lg = jnp.asarray(logits, jnp.float32)
    if spec.top_k is not None and spec.top_k < lg.shape[-1]:
        kth = jnp.sort(lg)[-spec.top_k]
        lg = jnp.where(lg >= kth, lg, -jnp.inf)
    return int(jax.random.categorical(key, lg / spec.temperature))
