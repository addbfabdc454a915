"""The load generator: one general generator for every traffic file.

A traffic mix is a JSON file under ``bench/traffic/`` that this module
reads; nothing else about a mix is code.  Its keys:

* ``loop``: ``"open"`` (independent users: arrivals on a schedule that does
  not wait for answers) or ``"closed"`` (callers that each wait for their
  answer before sending the next request);
* ``rate_per_s`` (open): mean arrival rate of a Poisson process;
* ``outstanding`` (closed): how many callers, each with one request in
  flight;
* ``pool`` (closed): how many distinct requests are made in set-up; the
  callers cycle through them;
* ``perturb``: the configuration's input perturbation (query noise as a
  share of the query's std, or the share of signs flipped).

Only this module calls into the program while the window runs, and it does
so through the public ``Runtime.submit`` / ``Runtime.result`` calls.
"""
from __future__ import annotations

import itertools
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CLOCK = time.monotonic
MAX_WAITERS = 1024


def open_loop_times(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop Poisson
    schedule.

    Every seed gets the same set of inter-arrival gaps, the quantiles of
    the exponential distribution at ``round(rate * seconds)`` points, in an
    order drawn from the seed: the offered load is the same in every run,
    and only its arrangement changes.
    """
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_per_s
    gaps = np.random.default_rng(seed).permutation(gaps)
    times = np.cumsum(gaps)
    # scale so the n arrivals fill [0, seconds) with the mean gap after the
    # last one, i.e. exactly the stated rate
    return times * (seconds / (times[-1] + gaps.mean()))


class Outcome:
    """What the generator saw of each request: when it was due or sent, when
    its answer reached the caller, and the answer (or the error); and the
    most threads the process ran while it drove the load."""

    def __init__(self, n: int):
        self.due = [math.nan] * n
        self.done = [math.nan] * n
        self.answer = [None] * n
        self.error = [None] * n
        self.index = list(range(n))  # which made request each slot served
        self.lateness: list = []
        self.threads = 0


def run_open(rt, engine: str, requests: list, times, t0: float,
             deadline: float) -> Outcome:
    """Submit ``requests[i]`` at ``t0 + times[i]``; a waiter thread per
    outstanding request stamps the moment its answer is available.
    ``deadline`` bounds how long the answers are waited for."""
    n = len(times)
    out = Outcome(n)

    def wait(i, gid):
        try:
            out.answer[i] = rt.result(gid, timeout=max(deadline - CLOCK(), 0.0))
            out.done[i] = CLOCK()
        except Exception as e:  # noqa: BLE001 - recorded as a failed request
            out.error[i] = repr(e)

    # one waiter per outstanding request, up to a cap that only an offered
    # load far past the knee reaches
    with ThreadPoolExecutor(max_workers=min(max(n, 1), MAX_WAITERS)) as pool:
        futs = []
        for i in range(n):
            due = t0 + float(times[i])
            delay = due - CLOCK()
            if delay > 0:
                time.sleep(delay)
            now = CLOCK()
            out.due[i] = due
            out.lateness.append(now - due)
            payload, kw = requests[i]
            try:
                gid = rt.submit(engine, payload, **kw)
            except Exception as e:  # noqa: BLE001 - a refused request
                out.error[i] = repr(e)
                continue
            futs.append(pool.submit(wait, i, gid))
            out.threads = max(out.threads, threading.active_count())
        for f in futs:
            f.result()
    return out


def run_closed(rt, engine: str, requests: list, clients: int, t0: float,
               t_end: float, deadline: float) -> Outcome:
    """``clients`` callers, each sending its next request once its last
    answer came, from ``t0`` until ``t_end``; the n-th request sent is
    ``requests[n % len(requests)]``.  ``due`` is the moment a request was
    sent."""
    taken = itertools.count()
    sent = [[] for _ in range(clients)]  # per caller: (n, due, done, ans, err)

    def caller(mine):
        while CLOCK() < t_end:
            n, due = next(taken), CLOCK()
            payload, kw = requests[n % len(requests)]
            try:
                gid = rt.submit(engine, payload, **kw)
                ans = rt.result(gid, timeout=max(deadline - CLOCK(), 0.0))
                mine.append((n, due, CLOCK(), ans, None))
            except Exception as e:  # noqa: BLE001 - recorded as failed
                mine.append((n, due, math.nan, None, repr(e)))
                return

    delay = t0 - CLOCK()
    if delay > 0:
        time.sleep(delay)
    threads = [threading.Thread(target=caller, args=(mine,), daemon=True)
               for mine in sent]
    for th in threads:
        th.start()
    threads_most = threading.active_count()
    for th in threads:
        th.join(max(deadline - CLOCK(), 0.0) + 1.0)
    rows = sorted((r for mine in sent for r in mine), key=lambda r: r[0])
    out = Outcome(len(rows))
    out.threads = threads_most
    for i, (n, due, done, ans, err) in enumerate(rows):
        out.index[i] = n % len(requests)
        out.due[i], out.done[i], out.answer[i], out.error[i] = \
            due, done, ans, err
    return out
