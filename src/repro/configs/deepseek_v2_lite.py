"""deepseek-v2-lite [moe]: 27L d=2048 16H, latent attention (MLA: kv_lora_rank
512, qk_nope 128 + qk_rope 64, v 128, no q_lora, YaRN x40), one leading dense
layer (d_ff 10944), then 26 DeepSeekMoE layers: 64 routed experts of width
1408, top-6 softmax greedy (weights not renormalised, scale 1) beside 2
shared experts; vocab 102400, untied, RMSNorm eps 1e-6.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434 §2.1-2.2]

The deployment the served configuration stands for: eight chips share each
MoE layer (expert parallelism 8), and this chip is chip ``share`` of them:
it holds routed experts ``8 * share .. 8 * share + 7`` of every MoE layer.
Attention, the dense layer, the shared experts and the vocabulary are
replicated on every chip (data-parallel attention, as in DeepSeek's own
serving).  ``full()`` is that chip's configuration; ``full(ep=1)`` is the
whole published model (15.7 B parameters).
"""
from repro.configs.common import ArchSpec
from repro.nn.layers import MLAConfig
from repro.nn.moe import MoEConfig
from repro.nn.transformer import ModelConfig

import jax.numpy as jnp

N_ROUTED = 64  # published n_routed_experts
EP = 8  # chips that share each MoE layer in the stated deployment


def mla(d_model: int = 2048, n_heads: int = 16, **kw) -> MLAConfig:
    base = dict(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, rope_theta=1e4, rope_factor=40.0,
                rope_original_max=4096, beta_fast=32.0, beta_slow=1.0,
                mscale=0.707, mscale_all_dim=0.707, eps=1e-6)
    return MLAConfig(d_model, n_heads, **{**base, **kw})


def full(ep: int = EP, share: int = 0) -> ModelConfig:
    held = N_ROUTED // ep
    return ModelConfig(
        name="deepseek-v2-lite", n_layers=27, d_model=2048, n_heads=16,
        n_kv_heads=16, d_ff=10944, vocab=102400, head_dim=192,
        block_pattern=("attn_moe",), first_dense=1, mla=mla(),
        moe=MoEConfig(d_model=2048, d_ff=1408, num_experts=N_ROUTED, top_k=6,
                      held=held, held_from=share * held, n_shared=2,
                      norm_topk=False, routed_scale=1.0),
        remat=False, param_dtype=jnp.bfloat16)


def smoke(ep: int = 4, share: int = 0) -> ModelConfig:
    """Every mechanism of ``full`` at CPU size: 1 dense + 4 MoE layers,
    latent 32 + rope 16, 4 heads, 8 routed experts (2 held: ``ep`` 4),
    top-2, 1 shared expert."""
    held = 8 // ep
    return ModelConfig(
        name="deepseek-v2-lite-smoke", n_layers=5, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, vocab=512, head_dim=24,
        block_pattern=("attn_moe",), first_dense=1,
        mla=mla(64, 4, kv_lora_rank=32, qk_nope_head_dim=8,
                qk_rope_head_dim=16, v_head_dim=8),
        moe=MoEConfig(d_model=64, d_ff=32, num_experts=8, top_k=2, held=held,
                      held_from=share * held, n_shared=1, norm_topk=False),
        remat=False)


SPEC = ArchSpec("deepseek-v2-lite", "moe", full, smoke,
                source="hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434")
