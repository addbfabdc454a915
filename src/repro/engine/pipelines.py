"""Built-in serving pipelines: NVSA RPM abduction, LVRF row decoding, LM decode.

Two deliberately different factorization workloads behind the same
``Engine.submit/step/drain`` API — NVSA factorizes padded block-code
attribute books (unitary algebra, F=3, M=10 padded, D=1024, stochastic
Gauss-Seidel sweeps) and ranks RPM candidates through probabilistic
abduction; LVRF decodes bipolar MAP row encodings against permutation-rolled
value atoms (F=3, M=n_values, D=2048, deterministic).  The engine sees both
as ServeSpecs; nothing in :mod:`repro.engine.engine` is NVSA-shaped.

``lm_decode`` is the third kind of workload: transformer serving
(`runtime/lm.LMEngine`'s prefill/decode) re-expressed as a registered
StageGraph + ``step_ops`` so the SAME adSCH machinery
(:func:`repro.engine.build.plan_interleave`,
:func:`repro.engine.engine.derive_sweeps_per_step`) prices LM steps; the
request loop lives in :class:`repro.runtime.LMEngine`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import vsa
from repro.core.scheduler import Op
from repro.engine.registry import ServeSpec, register
from repro.engine.stage import Stage, StageGraph
from repro.models import lvrf as lvrf_mod
from repro.models import nvsa as nvsa_mod


@register("nvsa_abduction")
def nvsa_abduction(key, *, cfg=None, params=None, batch: int = 8,
                   expected_sweeps: int | None = None,
                   fused_step: bool = False) -> ServeSpec:
    """NVSA RPM abduction.

    Engine requests: the 8 context-panel queries of one task ([8, D]), with
    ``meta={"cand": [8, D]}`` candidate queries; the postprocess runs the
    same beliefs -> abduce -> execute -> rank tail as :func:`nvsa.solve`,
    compiled once per spec, with one pull of its outputs a request.
    With ``params`` (a trained CNN) the ServeSpec also carries the runnable
    two-stage graph for stream serving.

    ``fused_step=True`` requests the fused Pallas sweep.  It only engages
    where :func:`repro.core.factorizer.fused_sweep_eligible` holds — the
    default NVSA config is unitary/Gauss-Seidel/stochastic, so there the
    flag is a documented no-op (the engine keeps the two-pass sweep and
    trajectories are unchanged); bipolar NVSA variants (``vsa.lanes == 1``)
    fuse for real.
    """
    import dataclasses as _dc

    cfg = cfg if cfg is not None else nvsa_mod.NVSAConfig()
    if fused_step and not cfg.factorizer.fused_step:
        cfg = _dc.replace(cfg, factorizer=_dc.replace(
            cfg.factorizer, fused_step=True))
    cbs, mask = nvsa_mod.make_codebooks(key, cfg)
    graph = nvsa_mod.stage_graph(params, cbs, mask, cfg, batch=batch,
                                 expected_sweeps=expected_sweeps)

    @jax.jit
    def abduction_tail(queries, scores, cand, codebooks, valid_mask):
        """The whole tail as ONE device program: run eagerly, each of its
        ~600 primitives is a dispatch of its own.  Codebooks and mask are
        arguments, not baked constants; ``cand=None`` traces the
        beliefs-only twin."""
        beliefs = nvsa_mod.beliefs_from_scores(queries, scores, valid_mask,
                                               cfg)
        if cand is None:
            return beliefs, None, None
        answer, sims = nvsa_mod.abduce_answers(beliefs[None], cand[None],
                                               codebooks, cfg)
        return beliefs, answer[0], sims[0]

    def postprocess(queries, res, meta):
        cand = meta["cand"] if meta is not None and "cand" in meta else None
        beliefs, answer, sims = jax.device_get(
            abduction_tail(queries, res.scores, cand, cbs, mask))
        out = {"indices": res.indices, "iterations": res.iterations,
               "converged": res.converged, "beliefs": beliefs}
        if cand is not None:
            out["answer"] = int(answer)
            out["sims"] = sims
        return out

    return ServeSpec("nvsa_abduction", cbs, cfg.factorizer, mask, graph,
                     postprocess)


@register("lvrf_rows")
def lvrf_rows(key, *, cfg=None, rules=("constant", "progression_p1",
                                       "distribute_three"),
              examples: int = 32, max_iters: int = 40,
              batch: int = 32, synchronous: bool = False,
              fused_step: bool = False) -> ServeSpec:
    """LVRF: decode row encodings and serve rule abduction/execution.

    Engine requests: row vectors [k, D] (products of permuted value atoms);
    results decode back to the (v1, v2, v3) values.  The stream graph
    encodes observed rows then scores them against the one-shot-learned rule
    codebook and executes the abduced rule over candidate completions.

    ``fused_step=True`` (with ``synchronous=True`` — Jacobi sweeps, which
    the fused kernel requires) serves the rows through the fused Pallas
    sweep: bit-identical trajectories to the unfused Jacobi path at half
    the per-iteration codebook HBM traffic.
    """
    cfg = cfg if cfg is not None else lvrf_mod.LVRFConfig()
    k_atoms, _ = jax.random.split(jnp.asarray(key))
    atoms = lvrf_mod.init_atoms(k_atoms, cfg)
    cbs = lvrf_mod.row_codebooks(atoms, cfg)
    fcfg = lvrf_mod.row_factorizer_config(
        cfg, max_iters=max_iters, synchronous=synchronous or fused_step,
        fused_step=fused_step)
    rows = lvrf_mod.make_rule_examples(np.random.default_rng(0), list(rules),
                                       cfg.n_values, examples)
    rule_vecs = lvrf_mod.learn_rules(atoms, jnp.asarray(rows), cfg)
    R, D, n = len(rules), cfg.vsa.dim, cfg.n_values

    def encode_fn(xs, key):
        return lvrf_mod.encode_row(atoms, xs["rows"], cfg), xs["prefix"]

    def abduce_fn(x, key):
        enc, prefix = x  # [B, K, D], [B, 2]
        sims = vsa.similarity(enc[:, :, None, :], rule_vecs)  # [B, K, R]
        post = jax.nn.softmax(sims.sum(1) * 8.0, axis=-1)
        return lvrf_mod.execute(atoms, rule_vecs, post, prefix, cfg)

    graph = StageGraph("lvrf_rows", (
        Stage("encode", encode_fn, symbolic=False, cost_ops=(
            Op("enc_bind", "simd", (batch * 2 * 3 * D,)),)),
        Stage("abduce", abduce_fn, symbolic=True, cost_ops=(
            Op("rule_sims", "gemm", (batch * 2, D, R), symbolic=True),
            Op("execute", "gemm", (batch * n, D, R), deps=("rule_sims",),
               symbolic=True),
            Op("rank", "simd", (batch * n * R,), deps=("execute",),
               symbolic=True),)),
    ))

    def postprocess(queries, res, meta):
        return {"values": res.indices, "iterations": res.iterations,
                "converged": res.converged,
                "reconstruction_sim": res.reconstruction_sim}

    return ServeSpec("lvrf_rows", cbs, fcfg, None, graph, postprocess)


def lm_stack_ops(cfg, tokens: int, tag: str, *, symbolic: bool,
                 lm_head: bool, kv_window: int = 0) -> tuple:
    """adSCH cost hints for pushing ``tokens`` tokens through one LM stack.

    Coarse by design (layers folded into the GEMM row dim, attention scored
    as its projections): the scheduler only needs relative magnitudes to
    size the decode burst against the prefill window.

    ``kv_window > 0`` adds the decode-attention KV read — the term that
    actually dominates decode HBM traffic: every token reads ``kv_window``
    cached positions per layer (contiguous: the full ``max_len`` row the
    dense einsum touches; paged: ``ceil(len/block) * block`` — the block
    gathers the flash-decode kernel issues).  Priced as a SIMD op (pure
    data movement), with int8 caches reading half the elements of bf16.
    """
    d, L = cfg.d_model, cfg.n_layers
    hd = cfg.head_dim if cfg.head_dim is not None else d // cfg.n_heads
    d_ff_in = 2 * cfg.d_ff if cfg.mlp_kind == "swiglu" else cfg.d_ff
    ops = [
        Op(f"{tag}_qkv", "gemm",
           (tokens * L, d, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd),
           symbolic=symbolic),
    ]
    attn_deps = (f"{tag}_qkv",)
    if kv_window:
        scale = 0.5 if cfg.kv_cache_dtype == "int8" else 1.0
        elems = int(tokens * L * kv_window * cfg.n_kv_heads * hd * 2 * scale)
        ops.append(Op(f"{tag}_kv_gather", "simd", (max(elems, 1),),
                      deps=(f"{tag}_qkv",), symbolic=symbolic))
        attn_deps = (f"{tag}_qkv", f"{tag}_kv_gather")
    ops += [
        Op(f"{tag}_attn_out", "gemm", (tokens * L, cfg.n_heads * hd, d),
           deps=attn_deps, symbolic=symbolic),
        Op(f"{tag}_mlp_in", "gemm", (tokens * L, d, d_ff_in),
           deps=(f"{tag}_attn_out",), symbolic=symbolic),
        Op(f"{tag}_mlp_out", "gemm", (tokens * L, cfg.d_ff, d),
           deps=(f"{tag}_mlp_in",), symbolic=symbolic),
    ]
    if lm_head:
        ops.append(Op(f"{tag}_lm_head", "gemm", (tokens, d, cfg.vocab),
                      deps=(f"{tag}_mlp_out",), symbolic=symbolic))
    return tuple(ops)


@register("lm_decode")
def lm_decode(key, *, cfg, batch: int = 4, prompt_len: int = 16,
              max_len: int | None = None,
              kv_block: int | None = None) -> ServeSpec:
    """LM continuous batching as a registered workload.

    ``cfg`` is a :class:`repro.nn.transformer.ModelConfig`.  The StageGraph
    maps LM serving onto the paper's interleave vocabulary: prefill is the
    big dense block (neural — grabs large cell groups), per-token decode is
    the small memory-bound kernel stream (declared ``symbolic`` so the
    adSCH policy fills it into leftover cells while another request's
    prefill owns the array — exactly the continuous-batching overlap
    question of Fig. 13b).  ``step_ops`` prices ONE decode token over the
    whole slot batch, so :func:`repro.engine.engine.derive_sweeps_per_step`
    returns how many decode steps fit a prefill window — the burst
    :class:`repro.runtime.LMEngine` runs between retirement scans, the same
    slot accounting as the factorizer ``Engine``.

    The decode stage now carries the KV-read term at the ``prompt_len``
    operating point: contiguous caches read the full ``max_len`` row per
    token (the dense einsum's traffic regardless of live length), paged
    caches (``kv_block`` set) read ``ceil((prompt_len+1)/kv_block)`` block
    gathers — so adSCH burst sizing and the Runtime's virtual-time fairness
    see paged decode's real (smaller) cost.
    """
    if kv_block is not None:
        kv_window = -(-(prompt_len + 1) // kv_block) * kv_block
    else:
        kv_window = max_len if max_len is not None else prompt_len
    graph = StageGraph("lm_decode", (
        Stage("prefill", None, symbolic=False,
              cost_ops=lm_stack_ops(cfg, batch * prompt_len, "prefill",
                                    symbolic=False, lm_head=False)),
        Stage("decode", None, symbolic=True,
              cost_ops=lm_stack_ops(cfg, batch, "decode", symbolic=True,
                                    lm_head=True, kv_window=kv_window)),
    ))

    def step_ops(slots, *, data_shards=1, model_shards=1):
        del model_shards  # LM tensor parallelism is out of the cell model's scope
        return list(lm_stack_ops(cfg, -(-slots // data_shards), "decode",
                                 symbolic=True, lm_head=True,
                                 kv_window=kv_window))

    return ServeSpec("lm_decode", graph=graph, step_ops=step_ops)
