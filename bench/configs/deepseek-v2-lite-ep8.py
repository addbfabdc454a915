"""DeepSeek-V2-Lite on one chip's share of an expert-parallel deployment,
served through ``Runtime`` -> ``LMEngine`` -> the paged ``ServeEngine``.

The model is built from the configuration file's published keys (every
width as published; ``n_routed_experts`` is the 8 experts this chip holds
of 64).  A request is a prompt of token ids with an exact output length;
the answer is the greedy tokens and the f32 logit of each.  ``correct``
teacher-forces the plain f32 reference (:mod:`repro.models.
deepseek_v2_ref`) on prompt + served tokens, after the engine is released,
and judges every generated position of every answer (see ``compare``).

    python3 bench/configs/deepseek-v2-lite-ep8.py --seed <n> --seconds <s>

runs the cell once (untraced) and prints, besides its result line, the
readings of the precision control: the reference recomputed with the cached
latent rounded to float8_e4m3fn and judged on the same answers as if it had
been served.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.common import seeds  # noqa: E402

# The first two numbers are in units of the reference's logit spread (the
# std of its logits over the vocabulary at that position), so they do not
# depend on the scale of the random weights.
#
# * logit_err_p99: the 99th percentile over every generated position of
#   |served logit - reference logit of the same token|.  The program stores
#   bf16 weights and activations and a bf16 latent cache and the reference
#   is f32 on the same weights: their difference is rounding, spread over
#   27 layers.  The percentile, not the maximum: the largest of ~29k
#   positions is one draw of the rounding's tail (on a TPU v5e the
#   program's reached 0.20, 2.3x below the float8 control's 0.46), where
#   the p99 separates program (0.076) and control (0.28) by 3.7x; the
#   limit lies near their geometric mean.  The maximum stays a reading
#   (``logit_err``).
# * argmax_gap_share: the share of generated positions at which the
#   reference's largest logit lies more than GAP above its logit of the
#   served token, i.e. the served token was not within rounding of the
#   reference's choice.
# * short, truncated: answers whose length is not the exact output length
#   asked for, and answers parked at the pool's capacity: every request is
#   served whole, so both are 0.
LIMITS = {"logit_err_p99": 0.15, "argmax_gap_share": 0.02, "short": 0,
          "truncated": 0}
GAP = 0.05
# the reference runs on sequences padded to the next of these lengths
# (causal, so the padding changes nothing before it): one compiled program
# per bucket, each a multiple of its attention block REF_BLOCK
REF_BLOCK = 512
REF_OUT = 512  # generated positions judged per call, padded


def model_config(conf: dict):
    """The served ``ModelConfig`` from the configuration file's keys."""
    from repro.nn.layers import MLAConfig
    from repro.nn.moe import MoEConfig
    from repro.nn.transformer import ModelConfig

    if conf["q_lora_rank"] is not None or conf["hidden_act"] != "silu" \
            or conf["scoring_func"] != "softmax" \
            or conf["topk_method"] != "greedy" or conf["moe_layer_freq"] != 1 \
            or conf["tie_word_embeddings"] or conf["attention_bias"]:
        raise ValueError("the configuration names a variant the program "
                         "does not serve")
    rs = conf["rope_scaling"]
    d, H = int(conf["hidden_size"]), int(conf["num_attention_heads"])
    dense = int(conf["first_k_dense_replace"])
    held = int(conf["n_routed_experts"])
    dep = conf["deployment"]
    mla = MLAConfig(
        d, H, kv_lora_rank=int(conf["kv_lora_rank"]),
        qk_nope_head_dim=int(conf["qk_nope_head_dim"]),
        qk_rope_head_dim=int(conf["qk_rope_head_dim"]),
        v_head_dim=int(conf["v_head_dim"]), rope_theta=float(
            conf["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_original_max=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]),
        eps=float(conf["rms_norm_eps"]))
    moe = MoEConfig(
        d_model=d, d_ff=int(conf["moe_intermediate_size"]),
        num_experts=int(dep["published_n_routed_experts"]),
        top_k=int(conf["num_experts_per_tok"]), held=held,
        held_from=int(dep["this_chip"]) * held,
        n_shared=int(conf["n_shared_experts"]),
        norm_topk=bool(conf["norm_topk_prob"]),
        routed_scale=float(conf["routed_scaling_factor"]))
    return ModelConfig(
        name=conf["name"], n_layers=int(conf["num_hidden_layers"]),
        d_model=d, n_heads=H, n_kv_heads=int(conf["num_key_value_heads"]),
        d_ff=int(conf["intermediate_size"]), vocab=int(conf["vocab_size"]),
        head_dim=mla.q_head_dim, block_pattern=("attn_moe",),
        first_dense=dense, mla=mla, moe=moe, remat=False,
        param_dtype=jnp.bfloat16)


def _judged(errs: list, gaps: list) -> dict:
    e = np.concatenate(errs) if errs else np.full(1, np.inf)
    gap = np.concatenate(gaps) if gaps else np.full(1, np.inf)
    return {"logit_err": float(e.max()),
            "argmax_gap_share": float(np.mean(gap > GAP)),
            "positions": int(e.size),
            "logit_err_p99": float(np.percentile(e, 99)),
            "logit_err_p999": float(np.percentile(e, 99.9)),
            "gap_share_2x": float(np.mean(gap > 2 * GAP)),
            "gap_max": float(gap.max())}


def mean_prompt(conf: dict) -> float:
    """Mean prompt length of the log-uniform request shape."""
    lo, hi = conf["prompt_min"], conf["prompt_max"]
    return (hi - lo) / math.log(hi / lo)


def make_requests(conf: dict, seed: int, count: int) -> list:
    """``count`` requests ``(prompt, {"max_new_tokens": n})``: prompt
    lengths log-uniform in [prompt_min, prompt_max], exact output lengths
    uniform in [output_min, output_max], ids uniform over the vocabulary."""
    rng = np.random.default_rng(seeds(seed, 1)[0])
    lo, hi = math.log(conf["prompt_min"]), math.log(conf["prompt_max"])
    out = []
    for _ in range(count):
        n = int(round(math.exp(rng.uniform(lo, hi))))
        gen = int(rng.integers(conf["output_min"], conf["output_max"] + 1))
        prompt = rng.integers(0, int(conf["vocab_size"]), n).astype(np.int32)
        out.append((prompt, {"max_new_tokens": gen}))
    return out


class Cell:
    name = "dsv2lite"

    def __init__(self, conf: dict, seed: int):
        from repro.lm.paging import PagedConfig
        from repro.nn import transformer as T
        from repro.runtime import LMEngine

        self.conf = conf
        self.cfg = model_config(conf)
        self.slots = int(conf["slots"])
        self.params, _ = T.init(jax.random.PRNGKey(seeds(seed, 1)[0]),
                                self.cfg)
        bs = int(conf["block_size"])
        max_len = int(conf["max_len"])
        paged = PagedConfig(block_size=bs, num_blocks=int(conf["pool_blocks"]),
                            max_blocks_per_slot=-(-max_len // bs),
                            prefill_chunk=int(conf["prefill_chunk"]))
        self.engine = LMEngine(self.cfg, self.params, slots=self.slots,
                               max_len=max_len,
                               prompt_len_hint=int(mean_prompt(conf)),
                               paged=paged)
        # four buckets spread over the longest sequence a request can make
        top = -(-(int(conf["prompt_max"]) + int(conf["output_max"]))
                // REF_BLOCK) * REF_BLOCK
        self.ref_buckets = sorted({max(REF_BLOCK, -(-top * k // 4 // REF_BLOCK)
                                       * REF_BLOCK) for k in (1, 2, 3, 4)})

    def release(self) -> None:
        self.engine = None
        gc.collect()

    # -- requests ----------------------------------------------------------
    def make_requests(self, seed: int, count: int, perturb: float) -> list:
        del perturb  # the traffic has none: prompts are drawn whole
        return make_requests(self.conf, seed, count)

    # -- what the served path answered -----------------------------------
    @staticmethod
    def record(req) -> dict:
        res = req.result
        return {"tokens": np.asarray(res["tokens"], np.int32),
                "logits": np.asarray(res["logits"], np.float32),
                "truncated": bool(res["truncated"])}

    @staticmethod
    def sweeps(req) -> int:
        """Tokens the request put through the model: prompt + generated."""
        return int(len(req.prompt) + len(req.result["tokens"]))

    # -- the plain reference -----------------------------------------------
    def reference(self, requests: list) -> list:
        """The requests themselves: the reference is teacher-forced on each
        answer's own tokens, so ``compare`` computes it per answer."""
        return list(requests)

    def ref_logits(self, prompt, tokens, latent_dtype=None) -> np.ndarray:
        """f32 reference logits [len(tokens), vocab] predicting each served
        token from prompt + the served tokens before it."""
        from repro.models import deepseek_v2_ref as ref

        seq = np.concatenate([prompt, tokens]).astype(np.int32)
        S = next((b for b in self.ref_buckets if b >= len(seq)),
                 -(-len(seq) // REF_BLOCK) * REF_BLOCK)
        G = len(tokens)
        pos = len(prompt) - 1 + np.arange(-(-G // REF_OUT) * REF_OUT)
        pos = np.minimum(pos, len(seq) - 1).astype(np.int32)
        padded = np.zeros(S, np.int32)
        padded[:len(seq)] = seq
        outs = [np.asarray(ref.forward_jit(
            self.params, self.cfg, jnp.asarray(padded),
            jnp.asarray(pos[i:i + REF_OUT]), latent_dtype=latent_dtype,
            q_block=REF_BLOCK))
            for i in range(0, len(pos), REF_OUT)]
        return np.concatenate(outs)[:G]

    def compare(self, got: list, want: list, control=None) -> dict:
        """Judge every generated position of every answer against the f32
        reference teacher-forced on the answer's own tokens (LIMITS above):
        per position, |served logit - reference logit of the token| and the
        reference's largest logit less that of the token, each over the std
        of the reference's logits there.  The reference runs once for each
        distinct (prompt, tokens) and only those three numbers a position
        are kept.  ``short`` counts answers of another length than asked
        for (an empty one among them: it has no position to judge) and
        ``truncated`` those parked at the pool's capacity.  Readings beside
        the limits: ``positions`` judged, the largest error and its
        p99.9, and the share of gaps over 2 x GAP.

        ``control`` (a dtype) also judges the reference with its latent
        rounded to that dtype, as if it had been served: at each position
        it emits its own argmax with that logit.  Its numbers come back
        under ``control``."""
        seen, errs, gaps, c_errs, c_gaps = {}, [], [], [], []
        truncated = short = 0
        for g, (prompt, kw) in zip(got, want):
            truncated += int(g["truncated"])
            short += int(len(g["tokens"]) != kw["max_new_tokens"])
            toks = g["tokens"]
            if len(toks) == 0:  # counted short above
                continue
            key = (prompt.tobytes(), toks.tobytes())
            if key not in seen:
                idx = np.arange(len(toks))
                low = None
                if control is not None:
                    lg = self.ref_logits(prompt, toks, control)
                    low = (lg.argmax(-1), lg.max(-1))
                ref = self.ref_logits(prompt, toks)
                seen[key] = (ref[idx, toks], ref.max(-1), ref.std(-1))
                if low is not None:
                    at = ref[idx, low[0]]
                    sd, mx = seen[key][2], seen[key][1]
                    c_errs.append(np.abs(low[1] - at) / sd)
                    c_gaps.append((mx - at) / sd)
            at, mx, sd = seen[key]
            errs.append(np.abs(g["logits"] - at) / sd)
            gaps.append((mx - at) / sd)
        nums = {"truncated": truncated, "short": short}
        nums.update(_judged(errs, gaps))
        if control is not None:
            nums["control"] = {**_judged(c_errs, c_gaps),
                               "truncated": truncated, "short": short}
        return nums

    # -- work counts -------------------------------------------------------
    def _mean_context(self) -> float:
        """Mean context of a token over the cell's request shape: a token at
        position t attends t + 1 positions."""
        c = self.conf
        rng = np.random.default_rng(0)
        n = np.exp(rng.uniform(math.log(c["prompt_min"]),
                               math.log(c["prompt_max"]), 4096))
        n = n + rng.integers(c["output_min"], c["output_max"] + 1, 4096)
        return float(np.sum(n * (n + 1) / 2) / np.sum(n))

    @property
    def row_flops(self) -> float:
        """Model operations of one token on this chip at the cell's mean
        context: 2 x the parameters it multiplies (attention, the dense or
        shared MLP, the router, its picks among the held experts, on
        average top_k x held / num_experts of them, and the LM head for
        the generated tokens alone, spread over all of a request's tokens)
        plus causal attention's scores and context, non-absorbed, over the
        mean context."""
        cfg, m, moe = self.cfg, self.cfg.mla, self.cfg.moe
        d, H = cfg.d_model, cfg.n_heads
        attn = d * H * m.q_head_dim + d * m.latent_dim + \
            m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim) + \
            H * m.v_head_dim * d
        expert = 3 * d * moe.d_ff
        picks = moe.top_k * moe.n_local / moe.num_experts
        n_moe = cfg.n_layers - cfg.first_dense
        c = self.conf
        prompt = mean_prompt(c)
        gen = (c["output_min"] + c["output_max"]) / 2
        params = cfg.n_layers * attn + cfg.first_dense * 3 * d * cfg.d_ff + \
            n_moe * (moe.n_shared * expert + d * moe.num_experts
                     + picks * expert) + d * cfg.vocab * gen / (prompt + gen)
        ctx = self._mean_context()
        return 2.0 * params + cfg.n_layers * ctx * H * 2 * (
            m.q_head_dim + m.v_head_dim)

    def sweep_work(self, n) -> tuple:
        """``(flops, HBM bytes)`` of the latent decode kernel over ``n``
        cached (row, position) pairs in every layer: each pair is read once
        (latent_dim bf16 lanes) and takes, for each head, a score over
        latent_dim lanes and a context update over kv_lora_rank."""
        m, L = self.cfg.mla, self.cfg.n_layers
        flops = n * L * m.n_heads * (m.latent_dim + m.kv_lora_rank) * 2
        return flops, n * L * m.latent_dim * 2


def build(conf: dict, seed: int) -> Cell:
    return Cell(conf, seed)


def main(argv=None) -> int:
    import time

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    seen = {}

    def both(cell, requests, served):
        got = [cell.record(a) for _, a in served]
        nums = cell.compare(got, [requests[i] for i, _ in served],
                            control=jnp.float8_e4m3fn)
        seen["control"] = nums.pop("control")
        return nums

    harness.check_answers = both
    res = harness.run_cell("dsv2lite.closed.long", args.seed, args.seconds,
                           False, t_start=t_start)
    print(json.dumps({"control": seen.get("control"),
                      "limits": LIMITS}), flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
