"""The configurations' request makers, codebooks and work counts, at tiny
sizes on the CPU."""
import json

import numpy as np
import pytest

from bench import harness

CELLS = {"nvsa": "nvsa.poisson.noisy", "lvrf": "lvrf.poisson.noisy"}
SMALL = {"slots": 8}


@pytest.fixture(scope="module")
def cells():
    out = {}
    for name, wl in CELLS.items():
        bench = harness.load_benchmark()
        _, conf, mod, _ = harness.resolve(bench, wl)
        out[name] = (mod, mod.build({**conf, **SMALL}, 2 ** 33 + 7))
    return out


def test_benchmark_names_its_files():
    bench = harness.load_benchmark()
    for wl in bench["workloads"]:
        _, conf, mod, traffic = harness.resolve(bench, wl["name"])
        assert conf["name"] == wl["config"]
        assert traffic["loop"] in ("open", "closed")
        for key in ("build",):
            assert hasattr(mod, key)
    for m in bench["per_layer"]:
        base = m["name"].split(".")[0]
        assert (harness.BENCH_DIR / "metrics" / f"{base}.py").is_file()
    assert json.loads((harness.BENCH_DIR / "peaks.json").read_text())[
        "devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_nvsa_tasks_are_raven_tasks(cells):
    mod, _ = cells["nvsa"]
    sizes = (5, 6, 10)
    for i in range(50):
        t = mod.make_task(np.random.default_rng([3, i]), sizes)
        assert t.shape == (16, 3)
        assert (t >= 0).all() and (t < np.asarray(sizes)).all()
        cands = {tuple(r) for r in t[8:]}
        assert len(cands) == 8  # distinct candidates, one of them right


def test_nvsa_requests_are_seeded(cells):
    _, cell = cells["nvsa"]
    a = cell.make_requests(5, 3, 1.4)
    b = cell.make_requests(5, 3, 1.4)
    c = cell.make_requests(6, 3, 1.4)
    assert len(a) == 3
    q, kw = a[0]
    assert q.shape == (8, 1024) and kw["meta"]["cand"].shape == (8, 1024)
    assert kw["keys"].shape == (8, 2)
    np.testing.assert_array_equal(a[2][0], b[2][0])
    assert not np.array_equal(a[2][0], c[2][0])
    # noise at 1.4 std leaves a query close to its clean binding but not on
    # it: the cosine of a clean unit query with its noisy copy is
    # 1 / sqrt(1 + 1.4^2) ~ 0.58
    clean = cell.make_requests(5, 1, 0.0)[0][0]
    noisy = cell.make_requests(5, 1, 1.4)[0][0]
    cos = (clean * noisy).sum(-1) / np.linalg.norm(clean, axis=-1) / \
        np.linalg.norm(noisy, axis=-1)
    assert np.all(np.abs(cos - 1 / np.sqrt(1 + 1.4 ** 2)) < 0.08)


def test_nvsa_codebooks_are_the_programs(cells):
    _, cell = cells["nvsa"]
    np.testing.assert_allclose(np.asarray(cell.cbs),
                               np.asarray(cell.engine.spec.codebooks),
                               rtol=0, atol=1e-6)


def test_lvrf_rows_flip_the_stated_share(cells):
    _, cell = cells["lvrf"]
    rows = cell.make_requests(9, 4, 0.1)
    clean = cell.make_requests(9, 4, 0.0)
    for (r, kw), (c, _) in zip(rows, clean):
        assert set(np.unique(r)) <= {-1.0, 1.0}
        assert int((r != c).sum()) == round(0.1 * 2048)
        assert kw["keys"].shape == (1, 2)
    np.testing.assert_array_equal(cell.make_requests(9, 4, 0.1)[3][0],
                                  rows[3][0])


def test_lvrf_codebooks_are_the_programs(cells):
    _, cell = cells["lvrf"]
    np.testing.assert_array_equal(np.asarray(cell.cbs),
                                  np.asarray(cell.engine.spec.codebooks))


@pytest.mark.parametrize("name", ["nvsa", "lvrf"])
def test_sweep_work_counts_rows_and_codebook_once(cells, name):
    _, cell = cells[name]
    f1, b1 = cell.sweep_work(1)
    f128, b128 = cell.sweep_work(128)
    assert f128 == pytest.approx(128 * f1)
    per_row = (b128 - b1) / 127
    assert b1 - per_row == 4 * cell.F * cell.cbs.shape[1] * cell.cbs.shape[2]
    assert cell.row_flops > 4 * cell.F * cell.cbs.shape[1] * \
        cell.cbs.shape[2]  # at least the two matmuls per factor


@pytest.mark.parametrize("name", ["nvsa", "lvrf"])
def test_reference_answers_clean_queries(cells, name):
    """On clean inputs the reference decodes the attributes it was given."""
    _, cell = cells[name]
    reqs = cell.make_requests(4, 2, 0.0)
    got = cell.reference(reqs)
    assert len(got) == 2
    for rec in got:
        assert np.all(rec["converged"])
