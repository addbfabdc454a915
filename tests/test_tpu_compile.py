"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  Each kernel is compiled natively
(``interpret=False``) at the shapes serving uses and must lower to a
``tpu_custom_call``: this catches what interpret mode accepts and the chip's
compiler refuses (block shapes against the (8, 128) tiling rule, VMEM
limits).  Nothing runs, so results are covered by tests/test_kernels.py.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_decode import kernel as fdk
from repro.kernels.resonator_step import kernel as rsk
from repro.kernels.similarity import kernel as simk
from repro.launch.programs import has_tpu_kernel


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32

# (N slots, D) at NVSA (D=1024) and LVRF (D=2048) widths; F=3, M=10
RESONATOR_WIDTHS = [(8, 1024), (32, 1024), (32, 2048), (256, 2048)]


@pytest.mark.parametrize("n,d", RESONATOR_WIDTHS)
@pytest.mark.parametrize("variant", ["dense", "masked", "local"])
def test_resonator_kernels_compile_for_v5e(one_chip, variant, n, d):
    F, M = 3, 10
    if variant == "dense":
        fn = lambda q, e, c: rsk.resonator_step_batch(q, e, c)
        extra = []
    elif variant == "masked":
        fn = lambda q, e, c, m: rsk.resonator_step_batch_masked(q, e, c, m)
        extra = [((F, M), jnp.bool_)]
    else:  # one model shard's rows of a 2-way row-sharded codebook
        M = M // 2
        fn = lambda q, e, c, m: rsk.resonator_step_batch_local(q, e, c, m)
        extra = [((F, M), F32)]
    compiled = _compile(fn, one_chip, ((n, d), F32), ((n, F, d), F32),
                        ((F, M, d), F32), *extra)
    assert has_tpu_kernel(compiled)


@pytest.mark.parametrize("kv_dtype", [BF16, I8])
def test_flash_decode_compiles_for_v5e(one_chip, kv_dtype):
    # llama3.2-3b heads: 24 query heads over G=8 KV heads, dh=128
    B, G, rep, dh, bs, W = 4, 8, 3, 128, 16, 6
    nbp = B * W + 1
    pool = [((nbp, bs, G, dh), kv_dtype)] * 2
    if kv_dtype == I8:
        fn = lambda q, k, v, t, ln, ks, vs: fdk.flash_decode(
            q, k, v, t, ln, k_scale=ks, v_scale=vs)
        pool += [((nbp, bs, G, 1), F32)] * 2
    else:
        fn = lambda q, k, v, t, ln: fdk.flash_decode(q, k, v, t, ln)
    q, k, v, *scales = [((B, G, rep, dh), F32)] + pool
    compiled = _compile(fn, one_chip, q, k, v, ((B, W), I32), ((B,), I32),
                        *scales)
    assert has_tpu_kernel(compiled)


@pytest.mark.parametrize("layered", [False, True])
def test_mla_decode_compiles_for_v5e(one_chip, layered):
    # deepseek-v2-lite: 16 heads over one 576-wide latent (512 value lanes),
    # 512-position blocks, 32 slots of up to 18 blocks; layered reads one
    # layer of the 26-layer stacked pool in place
    B, H, Dk, dv, bs, W = 32, 16, 576, 512, 512, 18
    pool = ((26, 65, bs, Dk) if layered else (65, bs, Dk), BF16)
    layer = ((), I32) if layered else None
    fn = lambda q, k, t, ln, *lay: fdk.flash_decode(  # noqa: E731
        q, k, None, t, ln, v_width=dv, layer=lay[0] if lay else None)
    compiled = _compile(fn, one_chip, ((B, H, Dk), F32), pool, ((B, W), I32),
                        ((B,), I32), *([layer] if layer else []))
    assert has_tpu_kernel(compiled)
    assert "mla_decode" in compiled.as_text()


@pytest.mark.parametrize("n", [1, 16])
def test_similarity_int8_compiles_for_v5e(one_chip, n):
    M, D = 10, 1024
    compiled = _compile(lambda q, w, s: simk.similarity_int8(q, w, s),
                        one_chip, ((n, D), F32), ((M, D), I8), ((M, 1), F32))
    assert has_tpu_kernel(compiled)
