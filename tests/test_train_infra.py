"""Training-infrastructure tests: loop resume, watchdog, optimizers, serving."""
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import optimizer as optim
from repro.train.loop import LoopConfig, StragglerWatchdog, run


class ToyData:
    def __init__(self):
        self._step = 0

    def state(self):
        return {"step": self._step}

    def restore(self, s):
        self._step = int(s["step"])

    def __iter__(self):
        while True:
            k = jax.random.PRNGKey(self._step)
            self._step += 1
            x = jax.random.normal(k, (16, 8))
            yield {"x": x, "y": x @ jnp.arange(8.0).reshape(8, 1)}


def _toy_step(opt):
    @jax.jit
    def step(state, batch):
        params, ostate = state

        def loss(p):
            return jnp.mean((batch["x"] @ p - batch["y"]) ** 2)

        l, g = jax.value_and_grad(loss)(params)
        params, ostate = opt.update(g, ostate, params)
        return (params, ostate), {"loss": l}

    return step


@pytest.mark.parametrize("make_opt", [
    lambda: optim.sgd(0.05), lambda: optim.adamw(0.05),
    lambda: optim.adafactor(0.3)])
def test_optimizers_converge(make_opt):
    opt = make_opt()
    params = jnp.zeros((8, 1))
    state = (params, opt.init(params))
    step = _toy_step(opt)
    data = iter(ToyData())
    state, m0 = step(state, next(data))
    for _ in range(500):
        state, m = step(state, next(data))
    assert float(m["loss"]) < float(m0["loss"]) / 50  # converging hard


def test_loop_checkpoint_resume():
    opt = optim.sgd(0.05)
    params = jnp.zeros((8, 1))
    with tempfile.TemporaryDirectory() as d:
        cfg = LoopConfig(total_steps=25, checkpoint_every=10, checkpoint_dir=d,
                         log_every=5)
        step = _toy_step(opt)
        state = (params, opt.init(params))
        final1, hist1 = run(step, state, ToyData(), cfg)
        # fresh state, same dir: resumes from step 20 and matches
        state2 = (params, opt.init(params))
        final2, hist2 = run(step, state2, ToyData(), cfg)
        np.testing.assert_allclose(np.asarray(final1[0]), np.asarray(final2[0]),
                                   atol=1e-6)
        assert hist2[0][0] >= 20  # resumed, did not restart from 0


def test_straggler_watchdog():
    w = StragglerWatchdog(factor=3.0, alpha=0.5)
    for _ in range(5):
        assert not w.observe(0.1)
    assert w.observe(1.0)  # 10x the EWMA -> flagged
    assert w.flagged == 1
    assert abs(w.ewma - 0.1) < 0.02  # straggler did not poison the mean


def test_schedules():
    wsd = optim.wsd_schedule(1.0, warmup=10, total=100, decay_frac=0.2)
    assert float(wsd(jnp.int32(5))) == pytest.approx(0.5)
    assert float(wsd(jnp.int32(50))) == pytest.approx(1.0)  # stable plateau
    assert float(wsd(jnp.int32(99))) < 0.3  # decaying
    cos = optim.cosine_schedule(1.0, warmup=10, total=100)
    assert float(cos(jnp.int32(100))) == pytest.approx(0.1, abs=1e-3)


def test_grad_clip():
    g = {"a": jnp.full((4,), 100.0)}
    clipped, norm = optim.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(200.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-4)
