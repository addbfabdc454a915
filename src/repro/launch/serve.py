"""LM serving CLI: a batch of prompts through :class:`repro.runtime.LMEngine`.

The CogSys system-level insight (adSCH interleaving, Sec. VI) maps to LM
serving as continuous batching: new requests are slotted into the fixed
decode batch as old ones finish, so the heterogeneous prefill/decode kernels
keep the array busy — the same utilization argument as Fig. 13b.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --smoke
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro import obs as obs_mod
from repro.configs.registry import ARCHS
from repro.launch.programs import enable_compile_cache
from repro.lm.paging import PagedConfig
from repro.nn import transformer as T
from repro.runtime.lm import LMEngine

log = logging.getLogger(__name__)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Chrome-trace JSON of the run to PATH")
    args = ap.parse_args()
    # The demo-main keeps its console output, but through logging (library
    # code must never print): a plain-message handler on this module's
    # logger, only when the app hasn't configured one itself.
    if not logging.getLogger().handlers and not log.handlers:
        handler = logging.StreamHandler()
        handler.setFormatter(logging.Formatter("%(message)s"))
        log.addHandler(handler)
        log.setLevel(logging.INFO)
    enable_compile_cache()
    spec = ARCHS[args.arch]
    cfg = spec.smoke() if args.smoke else spec.full()
    key = jax.random.PRNGKey(0)
    params, _ = T.init(key, cfg)
    log.info("%s: %s params; serving batch=%d",
             cfg.name, format(T.param_count(params), ","), args.batch)
    rec = obs_mod.Recorder() if args.trace else None
    eng = LMEngine(cfg, params, slots=args.batch,
                   max_len=args.prompt_len + args.gen + 1,
                   prompt_len_hint=args.prompt_len,
                   paged=PagedConfig() if args.paged else None, obs=rec)
    prompt = np.asarray(
        jax.random.randint(key, (args.prompt_len,), 0, cfg.vocab))
    t0 = time.perf_counter()
    for _ in range(args.batch):
        eng.submit(prompt, max_new_tokens=args.gen)
    done = eng.drain()
    dt = time.perf_counter() - t0
    snap = eng.snapshot()
    log.info("%d requests x %d tokens in %.1fms -> %.1f tok/s "
             "(%d prefill, %d decode dispatches; paged=%s)", len(done),
             args.gen, dt * 1e3, snap["tokens_total"] / dt,
             snap["prefill_dispatches"], snap["decode_dispatches"],
             snap["paged"])
    log.info("sample: %s", done[0].tokens[:16])
    if rec is not None:
        rec.write_chrome_trace(args.trace)
        log.info("trace written to %s (open in ui.perfetto.dev)", args.trace)


if __name__ == "__main__":
    main()
