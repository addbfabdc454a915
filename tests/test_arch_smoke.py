"""Per-arch smoke tests: reduced config, one train step + one decode step on
CPU, asserting output shapes and finiteness (assignment requirement f)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.nn import transformer as T


def _batch(cfg, B=2, S=32, key=jax.random.PRNGKey(1)):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.mrope_sections is not None:
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S)[None, None], (B, 3, S)).astype(jnp.int32)
        batch["vision_embeds"] = jax.random.normal(
            key, (B, cfg.vision_patches, cfg.d_model), jnp.bfloat16)
    if cfg.encoder is not None:
        batch["encoder_frames"] = jax.random.normal(
            key, (B, cfg.encoder.n_frames, cfg.encoder.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_smoke_train_step(arch_id):
    cfg = ARCHS[arch_id].smoke()
    params, logical = T.init(jax.random.PRNGKey(0), cfg)
    # logical tree mirrors params tree
    assert len(jax.tree.leaves(params)) == len(
        jax.tree_util.tree_leaves(logical, is_leaf=lambda x: isinstance(x, tuple)))
    batch = _batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(
        T.loss_fn, has_aux=True)(params, cfg, batch)
    assert jnp.isfinite(loss), arch_id
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads)), arch_id
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert gnorm > 0.0, f"{arch_id}: dead gradients"


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_smoke_decode_step(arch_id):
    cfg = ARCHS[arch_id].smoke()
    params, _ = T.init(jax.random.PRNGKey(0), cfg)
    B = 2
    if T.contiguous_unsupported_reason(cfg) is not None:
        # latent attention decodes from the paged latent pool only
        from repro.lm import model as lm_model
        pool = lm_model.init_pool(cfg, 2 * B, 8)
        tok = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab)
        logits, _, held = lm_model.decode_step_paged(
            params, cfg, pool, jnp.arange(2 * B, dtype=jnp.int32).reshape(B, 2),
            jnp.zeros((B,), jnp.int32), tok, jnp.ones((B,), bool),
            use_flash=False)
        assert logits.shape == (B, 1, cfg.vocab)
        assert bool(jnp.isfinite(logits).all()), arch_id
        assert held.shape == (B,)
        return
    cache = T.init_cache(cfg, B, 16)
    tok = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab)
    pos = jnp.zeros((B, 3, 1), jnp.int32) if cfg.mrope_sections is not None else None
    enc = None
    if cfg.encoder is not None:
        enc = jax.random.normal(jax.random.PRNGKey(3),
                                (B, cfg.encoder.n_frames, cfg.encoder.d_model),
                                jnp.bfloat16)
    logits, cache2 = T.decode_step(params, cfg, cache, tok, positions=pos, enc_out=enc)
    assert logits.shape == (B, 1, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), arch_id
    # caches advanced for attention archs
    for bi, kind in enumerate(cfg.block_pattern):
        if kind.startswith("attn"):
            assert int(cache2[bi]["self"]["len"][0, 0]) == 1
            break


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_full_config_matches_assignment(arch_id):
    """Exact published dimensions from the assignment table."""
    expect = {
        "qwen2-vl-72b": (80, 8192, 64, 8, 29568, 152064),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "minicpm-2b": (40, 2304, 36, 36, 5760, 122753),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
    }[arch_id]
    cfg = ARCHS[arch_id].full()
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab)
    assert got == expect, (arch_id, got, expect)


def test_moe_configs():
    assert ARCHS["granite-moe-3b-a800m"].full().moe.num_experts == 40
    assert ARCHS["granite-moe-3b-a800m"].full().moe.top_k == 8
    assert ARCHS["dbrx-132b"].full().moe.top_k == 4
    assert ARCHS["jamba-1.5-large-398b"].full().moe.top_k == 2


def test_jamba_interleave_ratio():
    pattern = ARCHS["jamba-1.5-large-398b"].full().block_pattern
    attn = sum(1 for k in pattern if k.startswith("attn"))
    mamba = sum(1 for k in pattern if k.startswith("mamba"))
    assert (attn, mamba) == (1, 7)  # 1:7 per assignment
    moe = sum(1 for k in pattern if k.endswith("moe"))
    assert moe == len(pattern) // 2  # MoE every other layer


def test_param_counts_sane():
    """Full-config param counts in the advertised ballpark (via eval_shape)."""
    approx = {"llama3.2-3b": (2.5e9, 4.5e9), "minicpm-2b": (2e9, 3.5e9),
              "starcoder2-3b": (2.5e9, 4e9), "xlstm-125m": (0.08e9, 0.3e9),
              "whisper-small": (0.2e9, 0.4e9), "qwen2.5-32b": (28e9, 36e9),
              "dbrx-132b": (110e9, 145e9), "qwen2-vl-72b": (65e9, 80e9),
              "jamba-1.5-large-398b": (330e9, 430e9),
              "granite-moe-3b-a800m": (2.5e9, 4e9)}
    from repro.nn.transformer import count_params_cfg
    for aid, (lo, hi) in approx.items():
        n, n_active = count_params_cfg(ARCHS[aid].full())
        assert lo < n < hi, (aid, f"{n:,}")
        assert n_active <= n
