"""chip_smoke.py's phases at smoke sizes on the CPU (Pallas interpret mode).

Each phase serves through the same Runtime -> engine path and makes the
same comparison it makes on the chip; only the sizes shrink and the kernel
check expects the interpreted (custom-call-free) lowering.  The script's
``main()`` itself must refuse a non-TPU device.
"""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.launch import programs

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _passed(rep, tol_key="tol"):
    assert rep["kernel"] == rep["kernel_expected"] is False
    assert rep[tol_key] is None or rep["max_diff"] <= rep[tol_key]


def test_main_refuses_a_non_tpu_device(smoke, capsys):
    assert smoke.main([]) != 0
    assert smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_compile_cache_dir_honours_env_else_fixed_path(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert programs.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = programs.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_nvsa_phase_matches_solve(smoke):
    _passed(smoke.nvsa_phase(0, tasks=1))


def test_lvrf_phase_fused_matches_unfused(smoke):
    _passed(smoke.lvrf_phase(0, rows=8, slots=8))


def test_lm_phase_paged_matches_contiguous(smoke):
    cfg = dataclasses.replace(ARCHS["llama3.2-3b"].smoke(),
                              param_dtype=jnp.bfloat16)
    rep = smoke.lm_phase(0, cfg, prompts=2, prompt_len=8, new_tokens=4)
    _passed(rep)
    assert "greedy_tokens=" in rep["detail"]


def test_sharded_phase_on_four_host_devices():
    """The --chips 4 phase on four fake host devices: 4x1 replicated NVSA and
    2x2 rows-sharded fused LVRF equal the one-device Engine."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        rep = cs.sharded_phase(0, tasks=1, rows=8, slots=8)
        print(json.dumps({{k: rep[k] for k in ("max_diff", "kernel",
                                               "kernel_expected")}}))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["kernel"] == rep["kernel_expected"] is False
    assert rep["max_diff"] == 0.0
