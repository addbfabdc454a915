"""Distribution tests on host devices (subprocess with 8 fake CPU devices)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_with_devices(code: str, n: int = 8) -> dict:
    """Run `code` in a subprocess with n fake devices; it must print JSON."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_pipeline_matches_sequential():
    r = run_with_devices(textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.distributed import pipeline as pp
        mesh = make_mesh((4,), ("pipe",))
        def layer(p, x):
            return jnp.tanh(x @ p["w"]) + x
        P, M, mb, d = 4, 6, 2, 16
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (P, d, d)) * 0.3}
        xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
        out_p = pp.pipeline_apply(layer, params, xs, mesh=mesh)
        out_s = pp.sequential_apply(layer, params, xs)
        err = float(jnp.abs(out_p - out_s).max())
        print(json.dumps({"err": err,
                          "bubble": pp.bubble_fraction(P, M)}))
    """))
    assert r["err"] < 1e-5
    assert abs(r["bubble"] - 3 / 9) < 1e-9


def test_sharded_train_matches_single_device():
    """The same train step on a (2,4) mesh and on 1 device must agree."""
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import ARCHS
        from repro.nn import transformer as T
        from repro.nn.common import sharding_ctx
        cfg = ARCHS["llama3.2-3b"].smoke()
        params, logical = T.init(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)}
        (l0, _), g0 = jax.value_and_grad(T.loss_fn, has_aux=True)(params, cfg, batch)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh, sharding_ctx(mesh):
            bs = jax.device_put(batch, NamedSharding(mesh, P("data")))
            (l1, _), g1 = jax.jit(jax.value_and_grad(
                lambda p, b: T.loss_fn(p, cfg, b), has_aux=True))(params, bs)
        gdiff = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(g0), jax.tree.leaves(g1)))
        print(json.dumps({"l0": float(l0), "l1": float(l1), "gdiff": gdiff}))
    """)
    r = run_with_devices(code)
    assert abs(r["l0"] - r["l1"]) < 2e-3
    assert r["gdiff"] < 2e-2


def test_gradient_compression_convergence():
    """INT8 all-reduce with error feedback trains a least-squares problem to
    (near) the same loss as exact fp32 all-reduce."""
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compression as C
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        key = jax.random.PRNGKey(0)
        Wt = jax.random.normal(key, (16, 4))
        X = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        Y = X @ Wt

        def loss(w, x, y):
            return jnp.mean((x @ w - y) ** 2)

        def train(compressed):
            w = jnp.zeros((16, 4))
            err = C.init_error_state({"w": w})

            @jax.jit
            @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P("data"), P("data"), P()),
                     out_specs=(P(), P()), check_vma=False)
            def step(w, x, y, e):
                g = jax.grad(loss)(w, x, y)
                if compressed:
                    qs, scales, e2 = C.compress_gradients({"w": g}, {"w": e})
                    gm = C.allreduce_compressed(qs, scales, "data")["w"]
                    return w - 0.05 * gm, e2["w"]
                return w - 0.05 * jax.lax.pmean(g, "data"), e

            e = err["w"]
            for _ in range(400):
                w, e = step(w, X, Y, e)
            return float(loss(w, X, Y))

        print(json.dumps({"exact": train(False), "int8": train(True)}))
    """)
    r = run_with_devices(code)
    assert r["exact"] < 1e-2
    assert r["int8"] < 5e-2  # converges despite 4x smaller wire format


def test_elastic_checkpoint_restore_across_meshes():
    """Save on a (4,2) mesh, restore onto (2,2) — elastic resharding."""
    code = textwrap.dedent("""
        import json, os, tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.train.checkpoint import CheckpointManager
        d = tempfile.mkdtemp()
        mesh1 = make_mesh((4, 2), ("data", "model"))
        tree = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                                    NamedSharding(mesh1, P("data", "model"))),
                "step": jnp.int32(7)}
        m = CheckpointManager(d, async_save=False)
        m.save(7, tree, extra={"data_state": {"step": 3}})
        assert m.latest_step() == 7
        mesh2 = make_mesh((2, 2), ("data", "model"),
                          devices=jax.devices()[:4])
        shardings = {"w": NamedSharding(mesh2, P("model", "data")), "step": None}
        restored, extra = m.restore(7, tree, shardings)
        ok = bool((np.asarray(restored["w"]) == np.arange(64.0).reshape(8, 8)).all())
        print(json.dumps({"ok": ok, "extra": extra,
                          "ndev": len(restored["w"].sharding.device_set)}))
    """)
    r = run_with_devices(code)
    assert r["ok"] and r["extra"] == {"data_state": {"step": 3}}
    assert r["ndev"] == 4  # restored onto the smaller mesh
