"""Readings of a cell's precision control, for setting its limits.

    python3 bench/control.py --workload nvsa.poisson.noisy --seconds 30 \
        --seeds 11,12,13

The control is the plain reference put in the program's place and computed
in bfloat16, the precision below the configuration's float32.  For each
seed it makes the requests a run of the cell makes (an open loop's arrivals
over ``--seconds``, a closed loop's pool), answers them with the bfloat16
reference, and prints the numbers ``correct`` compares against the float32
reference.  Each seed draws its own pool of requests.  A limit must lie
below the smallest of these readings.  The
benchmark's own runs never run the control.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(ses, seed: int, seconds: float) -> dict:
    import jax.numpy as jnp

    from bench import load

    cell, traffic = ses.cell, ses.traffic
    if traffic["loop"] == "open":
        n = len(load.open_loop_times(float(traffic["rate_per_s"]), seconds,
                                     seed))
    else:
        n = int(traffic["pool"])
    reqs = ses.requests(n, seed, pool_seed=seed)
    want = cell.reference(reqs, jnp.float32)
    got = cell.reference(reqs, jnp.bfloat16)
    return cell.compare(got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        ses = harness.Session(args.workload, seed)
        ses.cell.release()
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": readings(ses, seed, args.seconds),
                          "device": ses.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
