"""The precision control: the plain reference computed in bfloat16, put in
the program's place, must fail the comparison that decides ``correct``.

At the cells' own sizes on the chip its readings are in PERF.md (from
``bench/control.py``); here it runs on fewer requests, on the CPU.
"""
import jax.numpy as jnp
import pytest

from bench import harness

CASES = {"nvsa": ("nvsa.poisson.noisy", 24), "lvrf": ("lvrf.poisson.noisy",
                                                     256)}


@pytest.mark.parametrize("name", sorted(CASES))
def test_bfloat16_control_is_not_correct(name):
    workload, count = CASES[name]
    bench = harness.load_benchmark()
    _, conf, mod, traffic = harness.resolve(bench, workload)
    cell = mod.build({**conf, "slots": 8}, 2 ** 31 + 5)
    cell.release()
    reqs = cell.make_requests(2 ** 31 + 6, count, float(traffic["perturb"]))
    want = cell.reference(reqs, jnp.float32)
    got = cell.reference(reqs, jnp.bfloat16)
    checks = harness.limited(mod, cell.compare(got, want))
    assert any(v > lim for v, lim in checks.values()), checks
    # and the float32 reference against itself is exact
    same = cell.compare(want, want)
    assert all(v == 0 for v in same.values()), same
