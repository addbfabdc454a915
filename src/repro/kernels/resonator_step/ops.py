"""Public wrappers for the fused resonator step (backend dispatch).

:class:`FusedConfig` is the knob bundle the serving stack threads down to
the kernel (``Engine``/``ShardedEngine`` -> ``make_resonator`` -> here):
row-tile ceiling and an interpret override.  Everything else about the fused
path — eligibility, masking, shard offsets — is decided by the factorizer,
which owns the algebra.
"""
from __future__ import annotations

import dataclasses

from repro.kernels import resolve_interpret
from repro.kernels.resonator_step import kernel as _k
from repro.kernels.resonator_step import ref as _ref


@dataclasses.dataclass(frozen=True)
class FusedConfig:
    """Kernel-level knobs for the fused resonator sweep.

    ``tn`` caps the MXU row tile (:func:`kernel.row_tile` shrinks it for
    small or ragged N so zero-row padding stays bounded).  ``interpret``
    forces Pallas interpret mode on/off; ``None`` interprets off-TPU — the
    CPU CI/benchmark mode — and compiles on TPU.
    """

    tn: int = 128
    interpret: bool | None = None


DEFAULT_FUSED = FusedConfig()


def _cfg(fused: FusedConfig | None) -> FusedConfig:
    if fused is None:
        return DEFAULT_FUSED
    if not isinstance(fused, FusedConfig):
        # catch the natural misuse fused=True (the spec-level flag is the
        # bool `fused_step`) before it dies as an opaque AttributeError
        # inside a jit trace
        raise TypeError(
            f"fused= expects a FusedConfig or None, got {fused!r}; to "
            "request the fused sweep set fused_step=True on the "
            "FactorizerConfig / spec builder")
    return fused


def fused_resonator_step_batch(qs, est, codebooks, activation: str = "identity",
                               fused: FusedConfig | None = None):
    """One fused Jacobi resonator sweep over a query batch (bipolar algebra).

    qs: [N, D]; est: [N, F, D] -> (alpha [N, F, M], new_est [N, F, D]).
    Each (factor, row-tile) program reads the codebook from HBM once and
    amortises it over Tn queries with MXU-shaped matmuls; see
    kernels/resonator_step/kernel.py.
    """
    f = _cfg(fused)
    return _k.resonator_step_batch(qs, est, codebooks, activation=activation,
                                   tn=f.tn,
                                   interpret=resolve_interpret(f.interpret))


def fused_resonator_step_batch_masked(qs, est, codebooks, valid_mask,
                                      activation: str = "identity",
                                      fused: FusedConfig | None = None):
    """Mask-aware fused sweep: valid_mask [F, M] rides into VMEM with the
    codebook; invalid rows are neutralised before the activation and zeroed
    before the projection — bit-comparable to the masked two-pass path."""
    f = _cfg(fused)
    return _k.resonator_step_batch_masked(qs, est, codebooks, valid_mask,
                                          activation=activation, tn=f.tn,
                                          interpret=resolve_interpret(
                                              f.interpret))


def fused_resonator_step_batch_local(qs, est, cb_local, valid_mask_local=None,
                                     activation: str = "identity",
                                     fused: FusedConfig | None = None):
    """Shard-aware fused sweep over one model-shard's codebook row block:
    emits (raw local scores, partial un-saturated projection) for the
    caller's packed one-psum-per-factor gather."""
    f = _cfg(fused)
    return _k.resonator_step_batch_local(qs, est, cb_local, valid_mask_local,
                                         activation=activation, tn=f.tn,
                                         interpret=resolve_interpret(
                                             f.interpret))


def fused_resonator_step(q, est, codebooks, activation: str = "identity",
                         fused: FusedConfig | None = None):
    """One fused Jacobi resonator sweep for a single query (bipolar algebra).

    Halves per-iteration codebook HBM traffic vs separate similarity +
    projection matmuls; see kernels/resonator_step/kernel.py.
    """
    f = _cfg(fused)
    return _k.resonator_step(q, est, codebooks, activation=activation,
                             interpret=resolve_interpret(f.interpret))


resonator_step_ref = _ref.resonator_step_ref
resonator_step_batch_ref = _ref.resonator_step_batch_ref
resonator_step_batch_masked_ref = _ref.resonator_step_batch_masked_ref
resonator_step_batch_local_ref = _ref.resonator_step_batch_local_ref
