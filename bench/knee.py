"""Sweep the offered rate of an open-loop cell to find its knee.

    python3 bench/knee.py --workload nvsa.poisson.noisy --seed 7 \
        --seconds 10 --rates 10,20,30,40

One process builds the cell once, then runs one open-loop window per rate
(the cell's traffic with ``rate_per_s`` replaced), each after the last one
drained.  For each rate it prints the requests offered, those answered
inside the window, the backlog left at the window's close, the median and
95th-percentile latency, and the 95th percentile of the first and second
half of the window's arrivals: above the knee the backlog and the second
half's tail grow.  The knee is the highest rate that keeps up.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates per second")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench import harness, load

    ses = harness.Session(args.workload, args.seed)
    if ses.traffic["loop"] != "open":
        print("knee.py: the cell's traffic is not an open loop",
              file=sys.stderr)
        return 2
    rt = ses.start()
    rows = []
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            seed = args.seed + 1 + k
            times = load.open_loop_times(rate, args.seconds, seed)
            reqs = ses.requests(len(times), seed)
            t0 = load.CLOCK() + 0.1
            t_end = t0 + args.seconds
            out = load.run_open(rt, ses.cell.name, reqs, times, t0,
                                t_end + harness.GRACE_S)
            n = len(times)
            ok = [i for i in range(n) if out.answer[i] is not None]
            lat = np.asarray([out.done[i] - out.due[i] for i in ok]) * 1e3
            in_win = sum(1 for i in ok if out.done[i] <= t_end)
            half = n // 2
            first = [out.done[i] - out.due[i] for i in ok if i < half]
            second = [out.done[i] - out.due[i] for i in ok if i >= half]
            row = {"rate_per_s": rate, "offered": n, "answered": len(ok),
                   "answered_in_window": in_win,
                   "backlog_at_close": n - in_win,
                   "p50_ms": float(np.percentile(lat, 50)),
                   "p95_ms": float(np.percentile(lat, 95)),
                   "p95_first_half_ms": float(np.percentile(first, 95) * 1e3),
                   "p95_second_half_ms": float(np.percentile(second, 95)
                                               * 1e3),
                   "lateness_p99_ms": float(np.percentile(out.lateness, 99)
                                            * 1e3)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        rt.stop()
    print(json.dumps({"workload": args.workload, "device": ses.device,
                      "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
