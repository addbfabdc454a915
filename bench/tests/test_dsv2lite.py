"""The DeepSeek-V2-Lite cell at a size the CPU holds: its request maker,
its configuration file against the registered model, one traced run
through the harness with the float8 precision control judged on the same
answers, planted faults (a zeroed served expert, answers cut short), and
the readers of its per-layer metrics on spans and a small recorded
trace."""
import dataclasses
import json
import math
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

WL = "dsv2lite.closed.long"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# every mechanism at CPU widths: 1 dense + 4 MoE layers, latent 32 + rope
# 16, 4 heads, 2 of 8 routed experts held (top-2), 1 shared expert
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 8,
         "qk_rope_head_dim": 16, "v_head_dim": 8, "intermediate_size": 128,
         "moe_intermediate_size": 32, "num_hidden_layers": 5,
         "vocab_size": 512, "n_routed_experts": 2, "num_experts_per_tok": 2,
         "n_shared_experts": 1,
         "deployment": {"published_n_routed_experts": 8, "this_chip": 0},
         "slots": 4, "block_size": 8, "prefill_chunk": 8, "pool_blocks": 40,
         "max_len": 64, "prompt_min": 16, "prompt_max": 40, "output_min": 4,
         "output_max": 12}
TRAFFIC = {"outstanding": 6, "pool": 8}


@pytest.fixture(scope="module")
def cell():
    bench = harness.load_benchmark()
    _, conf, mod, traffic = harness.resolve(bench, WL)
    return conf, mod, traffic


def _run(trace, monkeypatch=None):
    seen = {}

    def both(cell, requests, served):
        got = [cell.record(a) for _, a in served]
        nums = cell.compare(got, [requests[i] for i, _ in served],
                            control=jnp.float8_e4m3fn)
        seen["control"] = nums.pop("control")
        return nums

    if monkeypatch is not None:
        monkeypatch.setattr(harness, "check_answers", both)
    res = harness.run_cell(
        WL, 2 ** 33 + 5, 1.5, trace, t_start=time.monotonic(), grace_s=30.0,
        require_tpu=False, conf_update=SMALL, traffic_update=TRAFFIC,
        cache_dir=None, peaks=PEAKS)
    return res, seen


def test_traffic_and_configuration(cell):
    conf, mod, traffic = cell
    assert traffic == {"loop": "closed", "outstanding": 48, "pool": 96,
                       "perturb": 0}
    assert conf["n_routed_experts"] == 8 and conf["reduced"] == [
        "n_routed_experts"]
    assert conf["deployment"]["published_n_routed_experts"] == 64
    from repro.configs import deepseek_v2_lite as D
    assert mod.model_config(conf) == dataclasses.replace(
        D.full(), name=conf["name"])


def test_request_lengths(cell):
    conf, mod, _ = cell
    reqs = mod.make_requests(conf, 2 ** 40 + 3, 2000)
    n = np.array([len(p) for p, _ in reqs])
    gen = np.array([kw["max_new_tokens"] for _, kw in reqs])
    assert n.min() >= 2048 and n.max() <= 8192
    # log-uniform: the median is the geometric mean of the ends, the mean
    # (hi - lo) / ln(hi / lo)
    assert abs(np.median(n) / 4096 - 1) < 0.06
    assert abs(n.mean() / (6144 / math.log(4)) - 1) < 0.03
    assert gen.min() >= 128 and gen.max() <= 512
    assert abs(gen.mean() / 320 - 1) < 0.03
    ids = np.concatenate([p for p, _ in reqs[:50]])
    assert ids.min() >= 0 and ids.max() < 102400 and ids.max() > 100000
    again = mod.make_requests(conf, 2 ** 40 + 3, 3)
    np.testing.assert_array_equal(again[2][0], reqs[2][0])


def test_run_is_correct_and_the_control_is_not(cell, monkeypatch):
    """A traced run serves every request whole and comes out correct; the
    reference with its latent rounded to float8_e4m3fn, judged on the same
    answers as if served, exceeds a limit."""
    _, mod, _ = cell
    res, seen = _run(True, monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["short"]["value"] == 0
    assert res["checks"]["truncated"]["value"] == 0
    ctl = seen["control"]
    assert any(ctl[k] > lim for k, lim in mod.LIMITS.items()), ctl
    shares = {k: res["metrics"][k]["value"]
              for k in ("prefill_share.rps", "decode_burst_share.rps")}
    assert all(0 < v < 100 for v in shares.values()), shares
    assert sum(shares.values()) < 100
    assert 0 < res["metrics"]["decode_occupancy.rps"]["value"] <= 100
    # a held expert takes at most one pick of each of the 4 slots' rows
    assert 0 < res["metrics"]["held_expert_load.rps"]["value"] <= 4
    # the CPU trace has no device plane: the kernel's reader finds nothing
    assert "mla_decode_roofline.rps" not in res["metrics"]


def test_planted_fault_is_not_correct(cell, monkeypatch):
    """One held expert's output zeroed in the served weights (the reference
    keeps them): the served logits leave the reference's."""
    resolve = harness.resolve

    def planted(*args, **kw):  # the run loads the module afresh
        wl, conf, mod, traffic = resolve(*args, **kw)
        init = mod.Cell.__init__

        def faulty(self, conf, seed):
            init(self, conf, seed)
            eng = self.engine
            blocks = eng._params["blocks"]
            down = blocks[0]["moe"]["down"].at[:, 0].set(0.0)
            params = {**eng._params, "blocks": [
                {**blocks[0], "moe": {**blocks[0]["moe"], "down": down}}]}
            eng._params = eng.serve.params = params

        monkeypatch.setattr(mod.Cell, "__init__", faulty)
        return wl, conf, mod, traffic

    monkeypatch.setattr(harness, "resolve", planted)
    res, _ = _run(False)
    assert res["correct"] is False, res["checks"]


def test_short_answers_are_not_correct(cell, monkeypatch):
    """Answers cut one token short: every served logit still matches the
    reference, and ``short`` alone makes the run not correct."""
    from repro.runtime import lm

    stop_at = lm.LMEngine._stop_at

    def early(self, req, produced):
        stop = stop_at(self, req, produced)
        return None if stop is None else stop - 1

    monkeypatch.setattr(lm.LMEngine, "_stop_at", early)
    res, _ = _run(False)
    assert res["correct"] is False
    checks = res["checks"]
    assert checks["short"]["value"] > 0 and checks["truncated"]["value"] == 0
    assert all(checks[k]["value"] <= checks[k]["limit"]
               for k in ("logit_err_p99", "argmax_gap_share")), checks


def _span(name, t0, t1, **args):
    return types.SimpleNamespace(track="dsv2lite", name=name, t0=t0, t1=t1,
                                 instant=False, args=args, sid=0,
                                 parent=None)


def test_readers_on_spans_and_a_recorded_trace(cell):
    """Shares of the step, slot occupancy and held-expert load from spans;
    the kernel's roofline share from the kernel's device events and the
    bursts' kv_tokens in the traced tail (the burst that straddles its
    start counted for its part inside)."""
    _, mod, _ = cell
    conf = {**cell[0], **SMALL}
    c = types.SimpleNamespace(cfg=mod.model_config(conf))
    work = lambda n: mod.Cell.sweep_work(c, n)  # noqa: E731
    spans = [_span("step", 0.0, 4.0), _span("fill", 0.0, 1.0),
             _span("prefill-chunk", 0.1, 0.9, slot=0, pos=0, tokens=8),
             _span("decode-burst", 1.0, 3.0, live=4, slots=4, steps=10,
                   kv_tokens=1000, held_picks=80, held_experts=8),
             _span("decode-burst", 3.0, 4.0, live=2, slots=4, steps=5,
                   kv_tokens=600, held_picks=20, held_experts=8)]
    trace = {"device": {"/device:TPU:0": {"XLA Ops": [
        ["%fusion.1 = f32[8] fusion()", 0.0, 5e8],
        ["%mla_decode.15 = f32[4,4,32] custom-call()", 1e9, 2e8],
        ["%mla_decode.16 = f32[4,4,32] custom-call()", 2e9, 2e8]]}},
        "anchor_ns": 0.0, "window_ns": [0.0, 4e9]}
    ctx = types.SimpleNamespace(
        trace=trace, lo=1e9, hi=3e9, planes=["/device:TPU:0"], window_s=2.0,
        host0=0.0, host1=4.0, spans=spans, engine="dsv2lite",
        sweep_work=work, peaks=PEAKS)
    load = lambda n: harness.load_module(  # noqa: E731
        harness.BENCH_DIR / "metrics" / f"{n}.py", f"bench_metric_{n}")
    assert load("prefill_share").read(ctx) == pytest.approx(20.0)
    assert load("decode_burst_share").read(ctx) == pytest.approx(75.0)
    # (4 x 10 + 2 x 5) live row-steps of 4 x 15
    assert load("decode_occupancy").read(ctx) == pytest.approx(100 * 50 / 60)
    # 100 picks over 15 steps x 8 (layer, held expert) pairs
    assert load("held_expert_load").read(ctx) == pytest.approx(100 / 120)
    bare = types.SimpleNamespace(**{**vars(ctx), "spans": spans[:3]})
    assert load("decode_occupancy").read(bare) is None
    assert load("held_expert_load").read(bare) is None
    # tail [2, 4]: half of the first burst (500 pairs) and all the second
    flops, nbytes = work(1100.0)
    least = max(flops / PEAKS["bf16_flops_per_s"],
                nbytes / PEAKS["hbm_bytes_per_s"])
    assert load("mla_decode_roofline").read(ctx) == pytest.approx(
        100 * least / 0.4)
    assert load("mla_decode_roofline").read(
        types.SimpleNamespace(**{**vars(ctx), "spans": []})) is None


def test_work_counts(cell):
    conf, mod, _ = cell
    c = types.SimpleNamespace(cfg=mod.model_config(conf), conf=conf)
    flops, nbytes = mod.Cell.sweep_work(c, 1)
    assert nbytes == 27 * 1152 and flops == 27 * 16 * (576 + 512) * 2
    c._mean_context = types.MethodType(mod.Cell._mean_context, c)
    per_token = mod.Cell.row_flops.fget(c)
    # ~1.06 G multiplied parameters a token, twice, plus attention
    assert 2.0e9 < per_token < 4.0e9
    assert json.dumps(conf)  # the configuration file is plain JSON
