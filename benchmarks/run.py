"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes artifacts/bench.json.
    PYTHONPATH=src python -m benchmarks.run [--only fig17 tab08 ...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()
    from repro.launch.programs import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (engine_serve, engine_sharded, factorizer_batch,
                            fault_recovery, kernels_micro, paper_hardware,
                            paper_tables, runtime_serve)

    mods = [paper_hardware, kernels_micro, paper_tables, engine_serve,
            engine_sharded, runtime_serve, fault_recovery]
    # the vmap-of-scalar baseline leg costs minutes in interpret mode, so the
    # factorizer comparison only runs when asked for (it also has its own
    # __main__ entry that writes BENCH_factorizer.json)
    if args.only and any("factorizer" in o for o in args.only):
        mods.insert(2, factorizer_batch)
    rows, failed = [], []
    for mod in mods:
        try:
            rows += mod.run()
        except Exception:  # report, run the rest, and fail the run at the end
            traceback.print_exc()
            failed.append(mod.__name__)
    if args.only:
        rows = [r for r in rows if any(o in r["benchmark"] for o in args.only)]
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['benchmark']}/{r['name']},{r['us_per_call']},\"{r['derived']}\"")
    os.makedirs("artifacts", exist_ok=True)
    with open("artifacts/bench.json", "w") as f:
        json.dump(rows, f, indent=1)
    if failed:
        print(f"failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
