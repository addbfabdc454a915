"""NVSA abduction on RAVEN tasks: the program's engine, its requests, and
the plain reference that decides ``correct``.

The configuration is ``nvsa-raven-d1024.json`` beside this file: block-code
VSA at D = 1024 in 4 blocks, F = 3 attributes (type 5, size 6, colour 10,
padded to 10), the unitary Gauss-Seidel resonator with score noise 0.3,
restarts every 20 sweeps, at most 60 sweeps, converged at cosine 0.55.
One request is one task: its 8 context-panel queries, with the 8 candidate
queries in ``meta["cand"]``; the answer comes from the program's
postprocess (beliefs -> rule abduction -> execution -> candidate ranking).

The reference below imports nothing of the program.  It makes its own
codebooks from the same seed-derived key, runs the same resonator
(including its per-query noise stream, which the pinned keys fix) in
float32 at the highest matmul precision, and the same abduction tail.
"""
from __future__ import annotations

import gc
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import in_blocks, seeds

HIGHEST = jax.lax.Precision.HIGHEST
REF_ROWS = 1024  # queries per reference block
REF_TASKS = 256  # tasks per reference abduction block

# The numbers that decide ``correct`` and their limits (PERF.md,
# "correct"), each between the largest reading of the program (float32,
# matmuls at JAX's default precision) and the smallest of its bfloat16
# control, on a TPU v5e at both cells' sizes: early2_mismatch program
# 0.0016 / 0.0006, control 0.0598 / 0.0149 (noisy / clean traffic);
# easy_answer_gap program 0 / 0, control 0.0415 / 0.0147; answer_mismatch
# program at most 0.0563 (noisy traffic, over four request pools), control
# 0.174 (noisy; the clean cell's control fails early2_mismatch).
LIMITS = {
    "early2_mismatch": 0.005,
    "easy_answer_gap": 0.006,
    "answer_mismatch": 0.11,
}

RULES = ("constant", "progression_p1", "progression_m1", "arithmetic_plus",
         "arithmetic_minus", "distribute_three")


# -- traffic: RAVEN center tasks at attribute level -------------------------

def _attr_grid(rule: str, n: int, rng) -> np.ndarray:
    g = np.zeros((3, 3), np.int64)
    if rule == "distribute_three":
        vals = rng.choice(n, size=3, replace=False)
        for r in range(3):
            g[r] = np.roll(vals, r)
        return g
    for r in range(3):
        a0 = rng.integers(0, n)
        if rule == "constant":
            row = (a0, a0, a0)
        elif rule == "progression_p1":
            row = (a0, (a0 + 1) % n, (a0 + 2) % n)
        elif rule == "progression_m1":
            row = (a0, (a0 - 1) % n, (a0 - 2) % n)
        elif rule == "arithmetic_plus":
            a1 = rng.integers(0, n)
            row = (a0, a1, (a0 + a1) % n)
        else:  # arithmetic_minus
            a1 = rng.integers(0, n)
            row = (a0, a1, (a0 - a1) % n)
        g[r] = row
    return g


def make_task(rng, sizes) -> np.ndarray:
    """One RAVEN center task: ``[16, F]`` attribute values, the 8 context
    panels (row-major, the 9th missing) then the 8 candidates, of which one
    completes the grid and seven change one or two of its attributes."""
    F = len(sizes)
    rules = [RULES[rng.integers(0, len(RULES))] for _ in range(F)]
    grids = [_attr_grid(r, n, rng) for r, n in zip(rules, sizes)]
    answer_attrs = tuple(int(g[2, 2]) for g in grids)
    answer = int(rng.integers(0, 8))
    cands, seen = [], {answer_attrs}
    for c in range(8):
        if c == answer:
            cands.append(answer_attrs)
            continue
        while True:
            attrs = list(answer_attrs)
            for a in rng.choice(F, size=rng.integers(1, 3), replace=False):
                attrs[a] = (attrs[a] + rng.integers(1, sizes[a])) % sizes[a]
            if tuple(attrs) not in seen:
                seen.add(tuple(attrs))
                break
        cands.append(tuple(attrs))
    ctx = np.stack([g.reshape(9)[:8] for g in grids], axis=-1)
    return np.concatenate([ctx, np.asarray(cands)], axis=0).astype(np.int32)


# -- block-code algebra, plain jnp --------------------------------------------

def _rfft(x, B, L):
    return jnp.fft.rfft(x.astype(jnp.float32).reshape(*x.shape[:-1], B, L),
                        axis=-1)


def _irfft(X, B, L, dt):
    return jnp.fft.irfft(X, n=L, axis=-1).reshape(
        *X.shape[:-2], B * L).astype(dt)


def _unitary(x, B, L, dt):
    """Project every block's spectrum onto unit magnitude; norm 1."""
    X = _rfft(x, B, L)
    X = X / (jnp.abs(X) + 1e-9)
    return (_irfft(X, B, L, jnp.float32) / jnp.sqrt(float(B))).astype(dt)


def _bind(atoms, B, L, dt):
    """Block-wise circular convolution of ``atoms[..., F, D]`` over F."""
    return _irfft(jnp.prod(_rfft(atoms, B, L), axis=-3), B, L, dt)


def _cos(x, y):
    num = jnp.sum(x * y, axis=-1)
    den = jnp.linalg.norm(x, axis=-1) * jnp.linalg.norm(y, axis=-1) + 1e-9
    return num / den


@partial(jax.jit, static_argnames=("F", "M", "B", "L"))
def make_codebooks(key, *, F, M, B, L):
    """``[F, M, D]`` real atoms whose per-block spectra have unit magnitude
    (random phases; the DC and Nyquist bins random signs)."""
    nf = L // 2 + 1
    k_ph, k_0, k_n = jax.random.split(key, 3)
    theta = jax.random.uniform(k_ph, (F, M, B, nf), minval=0.0,
                               maxval=2 * jnp.pi)
    spec = jnp.exp(1j * theta)
    s0 = jnp.where(jax.random.bernoulli(k_0, shape=(F, M, B)), 1.0, -1.0)
    spec = spec.at[..., 0].set(s0.astype(spec.dtype))
    sn = jnp.where(jax.random.bernoulli(k_n, shape=(F, M, B)), 1.0, -1.0)
    spec = spec.at[..., nf - 1].set(sn.astype(spec.dtype))
    x = jnp.fft.irfft(spec, n=L, axis=-1) / jnp.sqrt(jnp.float32(B))
    return x.reshape(F, M, B * L).astype(jnp.float32)


@partial(jax.jit, static_argnames=("B", "L"))
def make_queries(cbs, attrs, key_noise, key_fact, perturb, *, B, L):
    """Panel queries ``[T, 16, D]`` (bound attribute atoms plus Gaussian
    noise at ``perturb`` times each query's std) and the per-query PRNG
    keys ``[T, 8, 2]`` of the 8 context queries."""
    F = cbs.shape[0]
    atoms = cbs[jnp.arange(F), attrs]  # [T, 16, F, D]
    q = _bind(atoms, B, L, jnp.float32)
    q = q + perturb * jnp.std(q, axis=-1, keepdims=True) * \
        jax.random.normal(key_noise, q.shape)
    T = attrs.shape[0]
    keys = jax.random.split(key_fact, T * 8).reshape(T, 8, -1)
    return q, keys


# -- the plain reference ------------------------------------------------------

@partial(jax.jit, static_argnames=("p", "dt"))
def ref_factorize(cbs, mask, qs, keys, *, p, dt):
    """Resonator factorization of ``qs [N, D]``, one PRNG key per query.

    Gauss-Seidel sweeps: each factor is unbound from the query with the
    current estimates of the others, scored against its codebook, given
    noise at ``noise_std`` times the spread of its valid scores, and
    projected back (weights ``|score|``) onto unit spectrum.  A query stops
    when the bound argmax atoms reach cosine ``conv_threshold`` with it or
    after ``max_iters`` sweeps; every ``restart_every`` sweeps an
    unconverged query restarts from a random estimate.
    Returns (indices, iterations, converged, scores).
    """
    F, M, B, L, max_iters, noise, restart, thr = p
    D = B * L
    cb = cbs.astype(dt)
    maskf = mask.astype(dt)
    neg = jnp.asarray(-1e9, dt)
    q = qs.astype(dt)
    Q = _rfft(q, B, L)
    init = _unitary(jnp.einsum("fm,fmd->fd", maskf, cb, precision=HIGHEST),
                    B, L, dt)
    N = qs.shape[0]

    def scores_of(u, i):
        a = jnp.matmul(u, cb[i].T, precision=HIGHEST).astype(dt)
        return jnp.where(mask[i], a, neg)

    def sweep(s):
        est, iters, done, sim, k = s
        ks = jax.vmap(lambda kk: jax.random.split(kk, 2 * F + 2))(k)
        new, alphas = est, []
        for i in range(F):
            E = _rfft(new, B, L)
            U = Q * jnp.conj(jnp.prod(E, axis=1)) * E[:, i]
            a = scores_of(_irfft(U, B, L, dt), i)
            sigma = noise * jnp.std(jnp.where(mask[i], a, 0), axis=-1,
                                    keepdims=True)
            z = jax.vmap(lambda kk: jax.random.normal(kk, (M,)))(
                ks[:, 2 * i]).astype(dt)
            a = jnp.where(mask[i], a + sigma * z, a)
            w = jnp.abs(a) * maskf[i]
            proj = jnp.matmul(w, cb[i], precision=HIGHEST).astype(dt)
            new = new.at[:, i].set(_unitary(proj, B, L, dt))
            alphas.append(a)
        idx = jnp.argmax(jnp.stack(alphas, axis=1), axis=-1)
        rec = _bind(cb[jnp.arange(F), idx], B, L, dt)
        sim_new = _cos(rec, q)
        act = ~done & (iters < max_iters)
        new = jnp.where(act[:, None, None], new, est)
        sim_new = jnp.where(act, sim_new, sim)
        iters = iters + act.astype(jnp.int32)
        done = done | (sim_new >= thr)
        if restart:
            again = act & ~done & (iters % restart == 0)
            z = jax.vmap(lambda kk: jax.random.normal(kk, (F, D)))(
                ks[:, -2]).astype(dt)
            new = jnp.where(again[:, None, None],
                            _unitary(z, B, L, dt), new)
        return new, iters, done, sim_new, ks[:, -1]

    k0 = jax.vmap(lambda kk: jax.random.split(kk)[1])(keys)
    s = (jnp.broadcast_to(init, (N, F, D)), jnp.zeros(N, jnp.int32),
         jnp.zeros(N, bool), jnp.full(N, -1.0, dt), k0)
    est, iters, done, _, _ = jax.lax.while_loop(
        lambda s: jnp.any(~s[2] & (s[1] < max_iters)), sweep, s)
    E = _rfft(est, B, L)
    U = Q[:, None] * jnp.conj(jnp.prod(E, axis=1))[:, None] * E
    alpha = jnp.einsum("nfd,fmd->nfm", _irfft(U, B, L, dt), cb,
                       precision=HIGHEST).astype(dt)
    alpha = jnp.where(mask[None], alpha, neg)
    return (jnp.argmax(alpha, axis=-1).astype(jnp.int32), iters, done,
            alpha.astype(jnp.float32))


def _conv(p, q):
    """Circular convolution of distributions over Z_n: P(a + b = k)."""
    n = p.shape[-1]
    idx = (jnp.arange(n)[:, None] - jnp.arange(n)[None, :]) % n  # k - j
    return jnp.einsum("...j,...kj->...k", p, q[..., idx])


def _corr(p, q):
    """Circular correlation: P(a - b = k) = sum_j p[j + k] q[j]."""
    n = p.shape[-1]
    idx = (jnp.arange(n)[:, None] + jnp.arange(n)[None, :]) % n  # k + j
    return jnp.einsum("...kj,...j->...k", p[..., idx], q)


def _row_scores(p1, p2, p3):
    return jnp.stack([
        jnp.sum(p1 * p2 * p3, -1),
        jnp.sum(p1 * jnp.roll(p2, -1, -1) * jnp.roll(p3, -2, -1), -1),
        jnp.sum(p1 * jnp.roll(p2, 1, -1) * jnp.roll(p3, 2, -1), -1),
        jnp.sum(_conv(p1, p2) * p3, -1),
        jnp.sum(_corr(p1, p2) * p3, -1)], axis=-1)


def _predict(g):
    """Rule posterior from the two complete rows of ``g [..., 3, 3, n]``,
    then the posterior-weighted prediction of the missing panel."""
    score = _row_scores(g[..., 0, 0, :], g[..., 0, 1, :], g[..., 0, 2, :]) * \
        _row_scores(g[..., 1, 0, :], g[..., 1, 1, :], g[..., 1, 2, :])
    set0, set1 = g[..., 0, :, :].mean(-2), g[..., 1, :, :].mean(-2)
    d0 = 1 - jnp.sum(g[..., 0, 0, :] * g[..., 0, 1, :], -1)
    d1 = 1 - jnp.sum(g[..., 1, 0, :] * g[..., 1, 1, :], -1)
    match = jnp.sum(jnp.minimum(set0, set1) * 3.0, -1) / 3.0
    score = jnp.concatenate([score, (match ** 3 * d0 * d1)[..., None]], -1)
    post = score / (jnp.sum(score, -1, keepdims=True) + 1e-12)
    p7, p8 = g[..., 2, 0, :], g[..., 2, 1, :]
    srow = (g[..., 0, 0, :] + g[..., 0, 1, :] + g[..., 0, 2, :]) / 3.0
    d3 = jnp.clip(srow * (1 - p7) * (1 - p8), 0.0, None)
    preds = jnp.stack([(p7 + p8) / 2.0, jnp.roll(p8, 1, -1),
                       jnp.roll(p8, -1, -1), _conv(p7, p8), _corr(p7, p8),
                       d3 / (jnp.sum(d3, -1, keepdims=True) + 1e-12)], -2)
    pred = jnp.einsum("...r,...rn->...n", post, preds)
    return pred / (jnp.sum(pred, -1, keepdims=True) + 1e-12)


@partial(jax.jit, static_argnames=("sizes", "B", "L", "temp", "dt"))
def ref_answer(cbs, mask, ctx, scores, cand, *, sizes, B, L, temp, dt):
    """Beliefs (masked softmax of ``temp`` x score / |query|) of the 8
    context panels, then per attribute the rule abduction and execution,
    the predicted panel bound from expected atoms, and the candidates
    ranked by cosine.  Returns (answer [T], sims [T, 8])."""
    T = ctx.shape[0]
    cb = cbs.astype(dt)
    qn = jnp.linalg.norm(ctx.astype(dt), axis=-1)[..., None, None] + 1e-9
    logits = jnp.where(mask, temp * scores.astype(dt) / qn, -1e9)
    beliefs = jax.nn.softmax(logits, axis=-1)  # [T, 8, F, M]
    atoms = []
    for a, n in enumerate(sizes):
        g = beliefs[:, :, a, :n]
        g = g / (g.sum(-1, keepdims=True) + 1e-9)
        g = jnp.concatenate([g, jnp.full((T, 1, n), 1.0 / n, dt)], axis=1)
        pred = _predict(g.reshape(T, 3, 3, n))
        atoms.append(jnp.matmul(pred, cb[a, :n], precision=HIGHEST).astype(dt))
    pq = _bind(jnp.stack(atoms, axis=1), B, L, dt)
    sims = _cos(pq[:, None, :], cand.astype(dt))
    return jnp.argmax(sims, axis=-1), sims.astype(jnp.float32)


# -- the cell -------------------------------------------------------------------

class Cell:
    name = "nvsa"

    def __init__(self, conf: dict, seed: int):
        from repro import engine as eng
        from repro.core import factorizer as fz
        from repro.core import vsa
        from repro.models import nvsa

        self.conf = conf
        self.F = len(conf["attr_sizes"])
        self.M = int(conf["codebook_size"])
        self.D, self.B = int(conf["dim"]), int(conf["blocks"])
        self.L = self.D // self.B
        self.slots = int(conf["slots"])
        self.sizes = tuple(int(n) for n in conf["attr_sizes"])
        if self.sizes != tuple(nvsa.ATTR_SIZES):
            raise ValueError(f"attribute sizes {self.sizes} are not the "
                             f"program's {nvsa.ATTR_SIZES}")
        vcfg = vsa.VSAConfig(dim=self.D, blocks=self.B)
        fcfg = fz.FactorizerConfig(
            vsa=vcfg, num_factors=self.F, codebook_size=self.M,
            algebra=conf["algebra"], activation=conf["activation"],
            max_iters=int(conf["max_iters"]),
            noise_std=float(conf["noise_std"]),
            restart_every=int(conf["restart_every"]),
            conv_threshold=float(conf["conv_threshold"]),
            synchronous=bool(conf["synchronous"]))
        ncfg = nvsa.NVSAConfig(vsa=vcfg, factorizer=fcfg,
                               belief_temp=float(conf["belief_temp"]))
        key = jax.random.PRNGKey(seeds(seed, 4)[0])
        spec = eng.registry.build(conf["pipeline"], key, cfg=ncfg)
        self.engine = eng.Engine(spec, slots=self.slots)
        # the benchmark's own copy of the codebooks, for the reference
        self.cbs = make_codebooks(key, F=self.F, M=self.M, B=self.B,
                                  L=self.L)
        self.mask = jnp.stack([jnp.arange(self.M) < n for n in self.sizes])
        self.p = (self.F, self.M, self.B, self.L, int(conf["max_iters"]),
                  float(conf["noise_std"]), int(conf["restart_every"]),
                  float(conf["conv_threshold"]))

    def release(self) -> None:
        self.engine = None
        gc.collect()

    # -- requests ----------------------------------------------------------
    def make_requests(self, seed: int, count: int, perturb: float) -> list:
        s_task, s_noise, s_keys = seeds(seed, 4)[1:]
        attrs = np.stack([make_task(np.random.default_rng([s_task, i]),
                                    self.sizes) for i in range(count)])
        q, keys = make_queries(self.cbs, jnp.asarray(attrs),
                               jax.random.PRNGKey(s_noise),
                               jax.random.PRNGKey(s_keys),
                               jnp.float32(perturb), B=self.B, L=self.L)
        q, keys = np.asarray(q), np.asarray(keys)
        return [(q[i, :8], {"keys": keys[i], "meta": {"cand": q[i, 8:]}})
                for i in range(count)]

    # -- what the served path answered -----------------------------------
    @staticmethod
    def record(req) -> dict:
        res = req.result
        return {"indices": np.asarray(res["indices"]),
                "iterations": np.asarray(res["iterations"]),
                "converged": np.asarray(res["converged"]),
                "scores": np.asarray(req.factorization.scores, np.float32),
                "answer": int(res["answer"]),
                "sims": np.asarray(res["sims"], np.float32)}

    @staticmethod
    def sweeps(req) -> int:
        return int(np.sum(req.result["iterations"]))

    # -- the plain reference -----------------------------------------------
    def reference(self, requests: list, dtype=jnp.float32) -> list:
        with jax.default_matmul_precision("highest"):
            return self._reference(requests, dtype)

    def _reference(self, requests, dtype) -> list:
        T = len(requests)
        ctx = np.stack([p for p, _ in requests])  # [T, 8, D]
        keys = np.stack([kw["keys"] for _, kw in requests])
        cand = np.stack([kw["meta"]["cand"] for _, kw in requests])
        idx, iters, done, scores = in_blocks(
            lambda q, k: ref_factorize(self.cbs, self.mask, q, k, p=self.p,
                                       dt=dtype),
            [ctx.reshape(T * 8, -1), keys.reshape(T * 8, -1)], REF_ROWS)
        scores = scores.reshape(T, 8, self.F, self.M)
        answer, sims = in_blocks(
            lambda c, s, cd: ref_answer(
                self.cbs, self.mask, c, s, cd, sizes=self.sizes, B=self.B,
                L=self.L, temp=float(self.conf["belief_temp"]), dt=dtype),
            [ctx, scores, cand], REF_TASKS)
        idx, iters, done = (a.reshape(T, 8, *a.shape[1:])
                            for a in (idx, iters, done))
        return [{"indices": idx[t], "iterations": iters[t],
                 "converged": done[t], "scores": scores[t],
                 "answer": int(answer[t]), "sims": sims[t]}
                for t in range(T)]

    @staticmethod
    def compare(got: list, want: list) -> dict:
        """Numbers that compare the served answers with the reference's.

        The noisy sweeps are chaotic: once a query has not converged within
        a few sweeps, any rounding difference (between two float32
        formulations of the same algorithm, too) sends it down another
        trajectory.  So the numbers that decide ``correct`` look at what is
        settled early, with the task subset chosen by the reference alone:

        * ``early2_mismatch``: among the queries the reference converged
          within 2 sweeps, the share whose decoded indices, iteration count
          or converged flag differ;
        * ``easy_answer_gap``: over the tasks whose 8 queries the reference
          all converged within 3 sweeps, the widest gap by which the
          reference's similarity of the served answer lies below the
          reference's best candidate;
        * ``answer_mismatch``: over all tasks, the share whose served answer
          is not the reference's.  Chaotic trajectories move a few answers
          (a float32 reformulation does too), but a sweep state lost or
          corrupted between bursts moves many.

        Read beside them: ``traj_mismatch`` (all queries),
        ``early3_mismatch`` (within 3 sweeps) and ``easy_sims_gap`` (the largest candidate-similarity difference over
        the easy tasks).
        """
        n_q = traj = n_tasks = answers = 0
        n_early, early = [0, 0], [0, 0]  # within 2 and within 3 sweeps
        easy_gaps, easy_sims = [0.0], [0.0]
        for g, w in zip(got, want):
            same = (np.all(g["indices"] == w["indices"], axis=-1)
                    & (g["iterations"] == w["iterations"])
                    & (g["converged"] == w["converged"]))
            n_q += same.size
            traj += int(np.sum(~same))
            for j, k in enumerate((2, 3)):
                quick = w["converged"] & (w["iterations"] <= k)
                n_early[j] += int(np.sum(quick))
                early[j] += int(np.sum(quick & ~same))
            n_tasks += 1
            answers += int(g["answer"] != w["answer"])
            if np.all(w["converged"] & (w["iterations"] <= 3)):
                easy_gaps.append(float(w["sims"][w["answer"]]
                                       - w["sims"][g["answer"]]))
                easy_sims.append(float(np.abs(g["sims"] - w["sims"]).max()))
        return {"early2_mismatch": early[0] / max(n_early[0], 1),
                "early3_mismatch": early[1] / max(n_early[1], 1),
                "easy_answer_gap": max(easy_gaps),
                "traj_mismatch": traj / max(n_q, 1),
                "answer_mismatch": answers / max(n_tasks, 1),
                "easy_sims_gap": max(easy_sims)}

    # -- work of one sweep ---------------------------------------------------
    def fft_flops(self) -> float:
        """One real FFT of a D-vector, blockwise: 2.5 L log2 L per block."""
        return self.B * 2.5 * self.L * math.log2(self.L)

    @property
    def row_flops(self) -> float:
        """Operations of one Gauss-Seidel sweep of one query, counted from
        the algorithm: per factor the unbinding (a spectral product of the
        F + 1 spectra and one inverse FFT), the scores and the projection
        (2 M D each), and the unit-spectrum projection (forward and inverse
        FFT, one division per bin); then the convergence check (one
        inverse FFT of the bound atoms' spectra, and a cosine)."""
        F, M, D = self.F, self.M, self.D
        bins = self.B * (self.L // 2 + 1)
        fft = self.fft_flops()
        per_factor = (6 * (F + 1) * bins + fft + 4 * M * D + 2 * fft
                      + 8 * bins)
        return F * per_factor + 6 * F * bins + fft + 3 * D

    def sweep_work(self, n_rows: int) -> tuple:
        """``(flops, HBM bytes)`` of one sweep over ``n_rows`` slot rows.
        The bytes are the float32 codebooks, read once: the slot state and
        the queries stay in the chip's VMEM across a burst's sweeps (the
        compiled sweep keeps them in memory space 1)."""
        return n_rows * self.row_flops, 4 * self.F * self.M * self.D


def build(conf: dict, seed: int) -> Cell:
    return Cell(conf, seed)
