"""Kernel micro-benchmarks: compiled on a TPU, interpreted elsewhere
(interpret-mode wall time is NOT TPU-predictive; the derived column carries
the structural metrics that are)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import row, timeit
from repro.core.quantization import quantize
from repro.kernels import resolve_interpret
from repro.kernels.circconv import kernel as cck
from repro.kernels.circconv import ref as ccr
from repro.kernels.resonator_step import kernel as rsk
from repro.kernels.resonator_step import ref as rsr
from repro.kernels.similarity import kernel as simk


def run():
    interp = resolve_interpret()
    rows = []
    for n, L in [(64, 256), (256, 1024)]:
        x = jax.random.normal(jax.random.PRNGKey(0), (n, L))
        y = jax.random.normal(jax.random.PRNGKey(1), (n, L))
        t_k = timeit(lambda a, b: cck.circconv_rows(a, b, interpret=interp),
                     x, y, warmup=1, iters=3)
        t_r = timeit(jax.jit(ccr.circconv_rows_ref), x, y, warmup=1, iters=3)
        flops = 2 * n * L * L
        hbm = 3 * n * L * 4
        rows.append(row("kernels", f"circconv_rows(n={n},L={L})", t_k * 1e6,
                        f"intensity={flops/hbm:.0f}FLOP/B hbm_per_conv=O(d) "
                        f"ref_us={t_r*1e6:.0f}"))
    q = jax.random.normal(jax.random.PRNGKey(2), (64, 1024))
    w = quantize(jax.random.normal(jax.random.PRNGKey(3), (512, 1024)), "int8")
    t = timeit(lambda a: simk.similarity_int8(a, w.values, w.scale,
                                              interpret=interp), q,
               warmup=1, iters=3)
    rows.append(row("kernels", "similarity_int8(64x512x1024)", t * 1e6,
                    "codebook HBM traffic 1B/elem (4x less than fp32)"))
    # fused resonator sweep: [Tn, D]-tiled MXU matmuls, codebook read once per
    # (factor, row-tile) instead of once per query per factor
    N, F, M, D = 64, 3, 16, 512
    kb = jax.random.split(jax.random.PRNGKey(4), 3)
    sgn = lambda k, s: jnp.where(jax.random.bernoulli(k, shape=s), 1.0, -1.0)
    cbs = sgn(kb[0], (F, M, D))
    qs, est = sgn(kb[1], (N, D)), sgn(kb[2], (N, F, D))
    t_k = timeit(lambda a, b: rsk.resonator_step_batch(a, b, cbs,
                                                       interpret=interp),
                 qs, est, warmup=1, iters=3)
    t_r = timeit(jax.jit(lambda a, b: rsr.resonator_step_batch_ref(a, b, cbs)),
                 qs, est, warmup=1, iters=3)
    tiles = -(-N // rsk.row_tile(N))
    rows.append(row("kernels", f"resonator_step_batch(n={N},f={F},m={M},d={D})",
                    t_k * 1e6,
                    f"codebook_hbm_passes/iter={tiles} (vs {N} at batch-1) "
                    f"ref_us={t_r*1e6:.0f}"))
    # mask-aware fused sweep: the validity mask rides in VMEM with X[f], so
    # budget-masked serving keeps the single codebook pass per (f, row-tile)
    # (vs 2*tiles for the two-pass masked sweep the old guard fell back to)
    mask = jnp.stack([jnp.arange(M) < m for m in (5, M, 9)])
    t_m = timeit(lambda a, b: rsk.resonator_step_batch_masked(
        a, b, cbs, mask, interpret=interp), qs, est, warmup=1, iters=3)
    t_mr = timeit(jax.jit(lambda a, b: rsr.resonator_step_batch_masked_ref(
        a, b, cbs, mask)), qs, est, warmup=1, iters=3)
    rows.append(row("kernels",
                    f"resonator_step_batch_masked(n={N},f={F},m={M},d={D})",
                    t_m * 1e6,
                    f"codebook_hbm_passes/iter={tiles} (vs {2*tiles} unfused "
                    f"masked) mask_bytes/f={M*4} ref_us={t_mr*1e6:.0f}"))
    # shard-aware fused sweep: one model shard's row block; emits raw local
    # scores + the partial projection for the packed one-psum-per-factor
    # gather (psum payload 4*(M+D) B/row/factor, same as the unfused path)
    M2 = M // 2
    t_l = timeit(lambda a, b: rsk.resonator_step_batch_local(
        a, b, cbs[:, :M2], mask[:, :M2], interpret=interp), qs, est,
        warmup=1, iters=3)
    rows.append(row("kernels",
                    f"resonator_step_batch_local(n={N},f={F},m={M2},d={D})",
                    t_l * 1e6,
                    f"local_codebook_hbm_passes/iter={tiles} "
                    f"psum_payload_B/row/f={4*(M+D)}"))
    return rows
