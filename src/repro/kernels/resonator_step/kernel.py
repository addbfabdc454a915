"""Pallas TPU kernel: fused resonator iteration (bipolar MAP algebra), batched.

The factorizer's inner loop reads each codebook X[f] twice per iteration —
once for the similarity matmul, once for the projection.  This kernel keeps
the whole per-factor codebook resident in VMEM (M x D <= a few hundred KB at
workload scale) and runs unbind -> similarity -> activation -> projection ->
sign in ONE invocation: the codebook's HBM traffic halves and the unbound
estimate / score matrix never exist in HBM at all.

Grid: ``(F, N // Tn)`` with the row-tile axis innermost, so factor f's
codebook block index is constant across the inner sweep — Pallas fetches it
from HBM once per (factor, row-sweep) and amortises that single pass over Tn
queries.  Each program then issues two *real* MXU matmuls,
``[Tn, D] @ [D, M]`` (similarity) and ``[Tn, M] @ [M, D]`` (projection),
instead of the batch-1 vector-matrix products the pre-batched kernel did.
The all-factor estimate product (a [N, D] array) is precomputed outside (it
needs cross-factor data the grid cannot share) — everything per-factor is
fused.

Three entry points share that structure (and the serving stack uses all of
them — see core/factorizer.make_resonator):

  * :func:`resonator_step_batch` — the dense path (no validity mask);
  * :func:`resonator_step_batch_masked` — the codebook validity mask rides
    into VMEM alongside ``X[f]``: invalid rows are neutralised to ``-1e9``
    *before* the activation and zeroed *before* the projection, so masked
    fused output is bit-comparable to the masked two-pass reference
    (budget-masked continuous-batching serving runs this variant);
  * :func:`resonator_step_batch_local` — the shard-aware variant: given one
    ``model``-shard's codebook row block it emits the RAW local scores and
    the *partial* (un-saturated) projection, so a rows-sharded sweep can
    pack both into the one-psum-per-factor collective and apply the full
    mask + sign saturation after the gather (the same reassociated-sum
    exactness contract as the unfused model-sharded path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e9  # score neutraliser for invalid codebook rows (matches factorizer)


def row_tile(n: int, tn: int = 128) -> int:
    """Row-tile policy: MXU-shaped (>= 8, multiple of 8), sized so zero-row
    padding is bounded — N is split over the row-sweeps needed at the max
    tile rather than padded straight up to it (N=130 -> Tn=72, 14 pad rows;
    not Tn=128, 126 rows).  Exported so benchmarks report the same structural
    metrics the kernel actually uses."""
    if n < 1:
        raise ValueError(f"row_tile needs at least one row, got n={n}")
    if tn < 8 or tn % 8:
        raise ValueError(f"max row tile must be a multiple of 8 >= 8, got {tn}")
    tiles = -(-n // tn)
    rows_per_tile = -(-n // tiles)
    return max(8, -(-rows_per_tile // 8) * 8)


def _pad_rows(qs, est, tn: int):
    """Shared batch-entry prologue: row-tile choice + zero-row padding.

    Returns ``(qs, prod, est_t, tn, N, Np)`` with the pad-rows invariant
    checked EXPLICITLY rather than trusted to the ceil arithmetic: the padded
    row count must tile exactly, the tile must stay MXU-shaped, and fewer
    than one full tile of pad rows may exist — degenerate N (N < 8, or N no
    longer a multiple of 8 after an engine shrink ``resize``) must land here,
    not produce a silently misshapen grid.
    """
    N = qs.shape[0]
    prod = jnp.prod(est, axis=1)  # [N, D] cross-factor input
    tn = row_tile(N, tn)
    pad = (-N) % tn
    if pad:  # zero rows: sign(0) = +1, sliced off by the caller
        qs = jnp.pad(qs, ((0, pad), (0, 0)))
        prod = jnp.pad(prod, ((0, pad), (0, 0)))
        est = jnp.pad(est, ((0, pad), (0, 0), (0, 0)))
    Np = qs.shape[0]
    if tn < 8 or tn % 8 or Np % tn or not 0 <= pad < tn:
        raise AssertionError(
            f"pad-rows invariant violated: N={N} tn={tn} Np={Np} pad={pad}")
    return qs, prod, jnp.swapaxes(est, 0, 1), tn, N, Np  # est_t: [F, Np, D]


def _step_kernel(q_ref, prod_ref, est_ref, cb_ref, alpha_ref, new_est_ref,
                 *, use_abs: bool):
    q = q_ref[...].astype(jnp.float32)  # [Tn, D]
    prod = prod_ref[...].astype(jnp.float32)  # [Tn, D]
    est_f = est_ref[...][0].astype(jnp.float32)  # [Tn, D]
    X = cb_ref[...][0].astype(jnp.float32)  # [M, D] — resident for BOTH matmuls
    u = q * prod * est_f  # unbind (est^2 == 1)               [Tn, D]
    alpha = jax.lax.dot_general(  # similarity                [Tn, M]
        u, X, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    w = jnp.abs(alpha) if use_abs else alpha
    proj = jnp.dot(w, X, preferred_element_type=jnp.float32)  # [Tn, D]
    new_est_ref[...] = jnp.where(proj >= 0, 1.0, -1.0)[None].astype(
        new_est_ref.dtype)
    alpha_ref[...] = alpha[None].astype(alpha_ref.dtype)


def _masked_step_kernel(q_ref, prod_ref, est_ref, cb_ref, mask_ref,
                        alpha_ref, new_est_ref, *, use_abs: bool):
    """Mask-aware variant: ``mask_ref`` [1, 1, M] (1.0 = valid row) rides in
    VMEM next to the codebook.  Invalid rows are neutralised to ``-1e9``
    before the activation (so they can never win the argmax) and zeroed
    before the projection (so padded atoms never leak into the estimates) —
    exactly the two `where`s the unfused masked path applies."""
    q = q_ref[...].astype(jnp.float32)
    prod = prod_ref[...].astype(jnp.float32)
    est_f = est_ref[...][0].astype(jnp.float32)
    X = cb_ref[...][0].astype(jnp.float32)
    m = mask_ref[...][0].astype(jnp.float32)  # [1, M]
    u = q * prod * est_f
    alpha = jax.lax.dot_general(
        u, X, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    alpha = jnp.where(m > 0, alpha, _NEG)  # neutralise pre-activation
    w = (jnp.abs(alpha) if use_abs else alpha) * m  # zero pre-project
    proj = jnp.dot(w, X, preferred_element_type=jnp.float32)
    new_est_ref[...] = jnp.where(proj >= 0, 1.0, -1.0)[None].astype(
        new_est_ref.dtype)
    alpha_ref[...] = alpha[None].astype(alpha_ref.dtype)


def _local_step_kernel(q_ref, prod_ref, est_ref, cb_ref, mask_ref,
                       alpha_ref, proj_ref, *, use_abs: bool):
    """Shard-aware variant: ``cb_ref`` holds ONE model-shard's row block and
    ``mask_ref`` that block's slice of the full validity mask.  Emits the
    RAW local scores (the caller pads them to the full row range at its
    offset — disjoint supports make the psum gather bit-exact) and the
    *partial* projection of the locally-masked weights (fp32, NOT
    sign-saturated: saturation only applies to the full reassociated sum
    after the cross-shard psum)."""
    q = q_ref[...].astype(jnp.float32)
    prod = prod_ref[...].astype(jnp.float32)
    est_f = est_ref[...][0].astype(jnp.float32)
    X = cb_ref[...][0].astype(jnp.float32)  # [M_loc, D] local rows
    m = mask_ref[...][0].astype(jnp.float32)  # [1, M_loc]
    u = q * prod * est_f
    alpha = jax.lax.dot_general(
        u, X, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # [Tn, M_loc]
    w = jnp.where(m > 0, alpha, _NEG)
    w = (jnp.abs(w) if use_abs else w) * m
    proj_ref[...] = jnp.dot(w, X, preferred_element_type=jnp.float32)[None]
    alpha_ref[...] = alpha[None].astype(alpha_ref.dtype)  # raw: masked post-psum


@functools.partial(jax.jit, static_argnames=("activation", "tn", "interpret"))
def resonator_step_batch(qs: jax.Array, est: jax.Array, codebooks: jax.Array,
                         *, activation: str = "identity", tn: int = 128,
                         interpret: bool = False):
    """qs: [N, D]; est: [N, F, D] bipolar; codebooks: [F, M, D] ->
    (alpha [N, F, M], new_est [N, F, D])."""
    F, M, D = codebooks.shape
    qs, prod, est_t, tn, N, Np = _pad_rows(qs, est, tn)
    alpha, new_est = pl.pallas_call(
        functools.partial(_step_kernel, use_abs=activation == "abs"),
        grid=(F, Np // tn),  # rows innermost: codebook f stays VMEM-resident
        in_specs=[
            pl.BlockSpec((tn, D), lambda f, n: (n, 0)),  # q row tile
            pl.BlockSpec((tn, D), lambda f, n: (n, 0)),  # prod row tile
            pl.BlockSpec((1, tn, D), lambda f, n: (f, n, 0)),  # est_f rows
            pl.BlockSpec((1, M, D), lambda f, n: (f, 0, 0)),  # codebook f
        ],
        out_specs=[
            pl.BlockSpec((1, tn, M), lambda f, n: (f, n, 0)),
            pl.BlockSpec((1, tn, D), lambda f, n: (f, n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((F, Np, M), jnp.float32),
            jax.ShapeDtypeStruct((F, Np, D), est.dtype),
        ],
        interpret=interpret,
    )(qs, prod, est_t, codebooks)
    return (jnp.swapaxes(alpha, 0, 1)[:N],  # [N, F, M]
            jnp.swapaxes(new_est, 0, 1)[:N])  # [N, F, D]


@functools.partial(jax.jit, static_argnames=("activation", "tn", "interpret"))
def resonator_step_batch_masked(qs: jax.Array, est: jax.Array,
                                codebooks: jax.Array, valid_mask: jax.Array,
                                *, activation: str = "identity", tn: int = 128,
                                interpret: bool = False):
    """Mask-aware fused sweep.  valid_mask: [F, M] (bool or {0,1} float) ->
    (alpha [N, F, M] with invalid rows at -1e9, new_est [N, F, D])."""
    F, M, D = codebooks.shape
    qs, prod, est_t, tn, N, Np = _pad_rows(qs, est, tn)
    mask = valid_mask.astype(jnp.float32)[:, None, :]  # [F, 1, M]
    alpha, new_est = pl.pallas_call(
        functools.partial(_masked_step_kernel, use_abs=activation == "abs"),
        grid=(F, Np // tn),
        in_specs=[
            pl.BlockSpec((tn, D), lambda f, n: (n, 0)),
            pl.BlockSpec((tn, D), lambda f, n: (n, 0)),
            pl.BlockSpec((1, tn, D), lambda f, n: (f, n, 0)),
            pl.BlockSpec((1, M, D), lambda f, n: (f, 0, 0)),
            # validity mask f; the unit middle dim keeps the block's last
            # two dims equal to the array's, as the TPU (8, 128) tiling rule
            # requires of a (1, M) row of an (F, M) array
            pl.BlockSpec((1, 1, M), lambda f, n: (f, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tn, M), lambda f, n: (f, n, 0)),
            pl.BlockSpec((1, tn, D), lambda f, n: (f, n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((F, Np, M), jnp.float32),
            jax.ShapeDtypeStruct((F, Np, D), est.dtype),
        ],
        interpret=interpret,
    )(qs, prod, est_t, codebooks, mask)
    return (jnp.swapaxes(alpha, 0, 1)[:N],
            jnp.swapaxes(new_est, 0, 1)[:N])


@functools.partial(jax.jit, static_argnames=("activation", "tn", "interpret"))
def resonator_step_batch_local(qs: jax.Array, est: jax.Array,
                               cb_local: jax.Array,
                               valid_mask_local: jax.Array | None = None,
                               *, activation: str = "identity", tn: int = 128,
                               interpret: bool = False):
    """Shard-aware fused sweep over ONE model-shard's codebook row block.

    cb_local: [F, M_loc, D] (the local slice of the row-sharded codebooks);
    valid_mask_local: [F, M_loc] — the full mask's slice at this shard's row
    offset (``None`` = all valid).  Returns ``(alpha_loc [N, F, M_loc],
    part_proj [N, F, D])``: RAW local scores plus the fp32 partial
    projection of the locally-masked weights.  The caller zero-pads the
    scores to the full row range, packs both into one psum per factor, and
    sign-saturates the gathered projection — see factorizer.make_resonator.
    """
    F, M_loc, D = cb_local.shape
    qs, prod, est_t, tn, N, Np = _pad_rows(qs, est, tn)
    if valid_mask_local is None:
        valid_mask_local = jnp.ones((F, M_loc), jnp.float32)
    mask = valid_mask_local.astype(jnp.float32)[:, None, :]  # [F, 1, M_loc]
    alpha, proj = pl.pallas_call(
        functools.partial(_local_step_kernel, use_abs=activation == "abs"),
        grid=(F, Np // tn),
        in_specs=[
            pl.BlockSpec((tn, D), lambda f, n: (n, 0)),
            pl.BlockSpec((tn, D), lambda f, n: (n, 0)),
            pl.BlockSpec((1, tn, D), lambda f, n: (f, n, 0)),
            pl.BlockSpec((1, M_loc, D), lambda f, n: (f, 0, 0)),
            pl.BlockSpec((1, 1, M_loc), lambda f, n: (f, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tn, M_loc), lambda f, n: (f, n, 0)),
            pl.BlockSpec((1, tn, D), lambda f, n: (f, n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((F, Np, M_loc), jnp.float32),
            jax.ShapeDtypeStruct((F, Np, D), jnp.float32),
        ],
        interpret=interpret,
    )(qs, prod, est_t, cb_local, mask)
    return (jnp.swapaxes(alpha, 0, 1)[:N],  # [N, F, M_loc]
            jnp.swapaxes(proj, 0, 1)[:N])  # [N, F, D] partial, un-saturated


@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def resonator_step(q: jax.Array, est: jax.Array, codebooks: jax.Array,
                   *, activation: str = "identity",
                   interpret: bool = False):
    """Single-query wrapper: q: [D]; est: [F, D] bipolar; codebooks:
    [F, M, D] -> (alpha [F, M], new_est [F, D])."""
    alpha, new_est = resonator_step_batch(q[None], est[None], codebooks,
                                          activation=activation,
                                          interpret=interpret)
    return alpha[0], new_est[0]
