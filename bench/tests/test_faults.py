"""A run of each cell with its timed path broken underneath must come out
not correct; the same run unbroken must come out correct.

The runs skip the harness's look for a chip and drive everything else of a
run (set-up, the load generator through ``Runtime``, the window, the plain
reference and the comparison) at a size the CPU holds.  The faults are
the ones a one-chip serving cell can have: a sweep that leaves the state
unchanged, half of the slot rows left out of the sweep, the state a row
carries from one sweep burst to the next dropped, and an answer altered
where the postprocess produces it.  (No cell exchanges data between
chips.)  They run on the noisy cells, where rows live through many bursts.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from repro.core import factorizer as fz
from repro.engine import engine as eng
from repro.engine import registry

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# the cells' own slot count, at which the engine plans NVSA's bursts of 2
# sweeps (at 8 slots it would plan 8, and no row would outlive its burst)
SLOTS = 128
RUNS = {
    "nvsa": ("nvsa.poisson.noisy", {"rate_per_s": 16.0}, 2.0),
    "lvrf": ("lvrf.poisson.noisy", {"rate_per_s": 32.0}, 1.0),
}


def _broken_sweeps(orig, mode):
    def make(*args, **kw):
        rs = orig(*args, **kw)

        def sweep(qs, s):
            new = rs.sweep(qs, s)
            if mode == "unchanged":
                keep = jnp.ones(s.iters.shape, bool)
            else:  # every other slot row (half of them) is never computed
                keep = jnp.arange(s.iters.shape[0]) % 2 == 1
            return new._replace(
                est=jnp.where(keep[:, None, None], s.est, new.est),
                done=jnp.where(keep, s.done, new.done),
                sim=jnp.where(keep, s.sim, new.sim))

        return rs._replace(sweep=sweep)

    return make


def _dropped_state(orig):
    """Every sweep burst starts its rows from a fresh initial estimate, as
    if the state carried over from the last burst were lost."""
    def build(self):
        orig(self)
        run, rs = self._sweeps, self._rs

        def fresh(qs, s, budget):
            return run(qs, s._replace(est=rs.init(qs, s.keys).est), budget)

        self._sweeps = jax.jit(fresh)

    return build


def _altered_answers(orig):
    def build(name, key, **kw):
        spec = orig(name, key, **kw)
        post = spec.postprocess

        def altered(queries, res, meta):
            out = dict(post(queries, res, meta))
            if "answer" in out:
                out["answer"] = (out["answer"] + 1) % 8
            if "values" in out:
                v = np.array(out["values"])
                v[..., 0] = (v[..., 0] + 1) % 10
                out["values"] = v
            return out

        import dataclasses
        return dataclasses.replace(spec, postprocess=altered)

    return build


@pytest.mark.parametrize("fault", ["none", "unchanged", "half", "dropped",
                                   "answer"])
@pytest.mark.parametrize("cell", sorted(RUNS))
def test_fault_is_not_correct(cell, fault, monkeypatch):
    if fault in ("unchanged", "half"):
        monkeypatch.setattr(fz, "make_resonator",
                            _broken_sweeps(fz.make_resonator, fault))
    elif fault == "dropped":
        monkeypatch.setattr(eng.Engine, "_build_programs",
                            _dropped_state(eng.Engine._build_programs))
    elif fault == "answer":
        monkeypatch.setattr(registry, "build",
                            _altered_answers(registry.build))
    workload, traffic, seconds = RUNS[cell]
    res = harness.run_cell(
        workload, 2 ** 32 + 11, seconds, False, t_start=time.monotonic(),
        grace_s=20.0, require_tpu=False, conf_update={"slots": SLOTS},
        traffic_update=traffic, cache_dir=None, peaks=PEAKS)
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert res["correct"] is (fault == "none"), res["checks"]
    if fault == "dropped":  # caught by the number that covers many bursts
        name = {"nvsa": "answer_mismatch", "lvrf": "traj_mismatch"}[cell]
        assert res["checks"][name]["value"] > res["checks"][name]["limit"]


def test_traced_open_loop_run():
    """The traced run of an open-loop cell: per-layer metrics from the
    program's spans (the CPU trace has no device plane, so the readers of
    device events find nothing and leave their metrics out)."""
    res = harness.run_cell(
        "lvrf.poisson.noisy", 7, 1.0, True, t_start=time.monotonic(),
        grace_s=20.0, require_tpu=False, conf_update={"slots": 8},
        traffic_update={"rate_per_s": 20.0}, cache_dir=None, peaks=PEAKS)
    assert res["correct"] is True, res["checks"]
    assert {"queue_wait_share.p50", "host_share.p50"} <= set(res["metrics"])
    assert "device_idle_share.p50" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0
