"""Run one benchmark cell and assemble its result line.

Everything a cell is made of is found by name from ``BENCHMARK.json``:

* the configuration: its JSON file (``configs[].file``) and, beside it, a
  Python module of the same name that builds the program's engine from the
  seed, makes the requests, reads what the served path answered, holds the
  plain reference, and counts the work of one sweep;
* the traffic mix: ``bench/traffic/<traffic>.json``, read by ``load.py``;
* each per-layer metric: ``bench/metrics/<base>.py`` where ``<base>`` is
  the metric's name up to its first ``.`` (``host_share.p95`` and
  ``host_share.rps`` share ``host_share.py``); its ``read(ctx)`` returns a
  number, or ``None`` when the cell gives it nothing to read.

End-to-end metrics are computed here from what the load generator saw:
``latency_p<q>_ms`` (the q-th percentile of due-time-to-answer over every
request due in the window), ``throughput_rps`` (answers that reached their
callers inside the window, per second of window) and ``setup_s``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import re
import shutil
import sys
import threading
import time
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"
CACHE_DIR = ROOT / ".bench_cache" / "jax"
GRACE_S = 60.0  # how long past the window's close an answer may still come
TRACE_TAIL_S = 5.0  # a traced run profiles the last seconds of its window
# (its spans and answers are read over the whole window)
WATCHDOG_S = 600.0
# The deployment: its codebooks and its pool of requests are the same in
# every run (the program bakes codebooks into its compiled programs, so
# codebooks drawn per run would recompile them in every set-up); a run's
# seed draws the order of the requests and the arrival schedule.
CODEBOOK_SEED, POOL_SEED, WARM_SEED = 0, 1, 2
CLOCK = time.monotonic


class CellError(Exception):
    """The cell cannot run here (no accelerator, too few chips, a missing
    file): the run exits non-zero and prints no result."""


# -- finding a cell ----------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise CellError(f"{path} not found")
    with open(path) as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise CellError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path, name: str):
    """Import a Python file by path (file names may hold ``-`` and ``.``)."""
    if not path.is_file():
        raise CellError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str, root: Path = ROOT):
    """``(workload entry, config JSON, config module, traffic dict)``."""
    wl = _by_name(bench["workloads"], workload, "workload")
    centry = _by_name(bench["configs"], wl["config"], "configuration")
    cpath = root / centry["file"]
    if not cpath.is_file():
        raise CellError(f"{cpath} not found")
    with open(cpath) as f:
        conf = json.load(f)
    mod = load_module(cpath.with_suffix(".py"),
                      "bench_config_" + re.sub(r"\W", "_", wl["config"]))
    tpath = root / "bench" / "traffic" / f"{wl['traffic']}.json"
    if not tpath.is_file():
        raise CellError(f"{tpath} not found")
    with open(tpath) as f:
        traffic = json.load(f)
    return wl, conf, mod, traffic


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


# -- JAX set-up --------------------------------------------------------------

def configure_jax(cache_dir: Path | None = CACHE_DIR):
    """Point JAX's persistent compilation cache at its fixed directory in
    the checkout and cache every program, however quick to compile
    (``cache_dir=None`` leaves JAX's cache settings alone)."""
    import jax

    if cache_dir is not None:
        Path(cache_dir).mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


class CompileClock:
    """Counts the programs JAX compiles and those it loads from the
    persistent cache (its monitoring events), with their names, and the
    functions it traces."""

    def __init__(self):
        import jax

        self.requests: list = []  # (name, seconds): compiled or loaded
        self.loaded = 0
        self.traced = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.requests.append((kw.get("fun_name", "?"), duration))
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            with self._lock:
                self.traced += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.loaded += 1

    def snapshot(self) -> tuple:
        """``(programs compiled, seconds they took, programs loaded)``."""
        with self._lock:
            n = len(self.requests)
            return (n - self.loaded, sum(d for _, d in self.requests),
                    self.loaded)


class GcPauses:
    """Times the interpreter's garbage-collection passes while it is open
    (``gc.callbacks``): a pass holds every thread of the process."""

    def __init__(self):
        self.passes: list = []  # (generation, seconds)
        self._t = None
        gc.callbacks.append(self._event)

    def _event(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = CLOCK()
        elif self._t is not None:
            self.passes.append((info["generation"], CLOCK() - self._t))

    def close(self) -> str:
        gc.callbacks.remove(self._event)
        if not self.passes:
            return "no garbage-collection pass"
        gen, longest = max(self.passes, key=lambda p: p[1])
        full = sum(1 for g, _ in self.passes if g == 2)
        return (f"{len(self.passes)} garbage-collection passes ({full} full),"
                f" {sum(d for _, d in self.passes) * 1e3:.3f} ms in all, "
                f"longest {longest * 1e3:.3f} ms (generation {gen})")


def device_report(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise CellError(f"the first device is {devs[0].platform!r}, not a "
                        "TPU; this benchmark measures the accelerator only")
    if len(devs) < chips:
        raise CellError(f"the cell needs {chips} chips, JAX finds "
                        f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_memory(jax) -> int | None:
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_peaks(kind: str) -> dict | None:
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)["devices"]
    return table.get(kind)


# -- end-to-end metrics ------------------------------------------------------

_PCTL = re.compile(r"latency_p(\d+(?:\.\d+)?)_ms$")


def end_to_end(name: str, lat_s: list, completed_in_window: int,
               seconds: float, setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "throughput_rps":
        return completed_in_window / seconds
    m = _PCTL.match(name)
    if m:
        return float(np.percentile(np.asarray(lat_s), float(m.group(1)))
                     * 1e3)
    raise CellError(f"no rule computes the end-to-end metric {name!r}")


# -- the traced part of a run ------------------------------------------------

class Profiler:
    """Profiles ``[t_end - length, t_end]`` of the window from a thread of
    its own, with an anchor annotation at its start that ties the trace's
    clock to the host clock the spans use."""

    def __init__(self, jax, engine, t_end: float, length: float,
                 log_dir: Path):
        self.jax, self.engine = jax, engine
        self.t_start, self.t_end = t_end - length, t_end
        self.log_dir = log_dir
        self.marks: dict = {}
        self.error = None
        if log_dir.exists():
            shutil.rmtree(log_dir)
        log_dir.mkdir(parents=True)
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        from bench.trace import ANCHOR
        jax = self.jax
        try:
            delay = self.t_start - CLOCK()
            if delay > 0:
                time.sleep(delay)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.log_dir),
                                     profiler_options=opts)
            self.marks["sweeps0"] = self.engine.sweeps_total
            self.marks["host0"] = CLOCK()
            with jax.profiler.TraceAnnotation(ANCHOR):
                pass
            delay = self.t_end - CLOCK()
            if delay > 0:
                time.sleep(delay)
            self.marks["host1"] = CLOCK()
            self.marks["sweeps1"] = self.engine.sweeps_total
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - reported, the run fails
            self.error = e

    def join(self):
        self.thread.join()
        if self.error is not None:
            raise self.error


def per_layer(metrics: list, ctx) -> dict:
    out = {}
    for m in metrics:
        base = m["name"].split(".")[0]
        mod = load_module(BENCH_DIR / "metrics" / f"{base}.py",
                          f"bench_metric_{base}")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- one run -----------------------------------------------------------------

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Session:
    """One cell opened for runs: the JAX set-up, the device, the compile
    clock and the configuration's built cell (the program's engine)."""

    def __init__(self, workload: str, seed: int, *, require_tpu: bool = True,
                 conf_update=None, traffic_update=None, root: Path = ROOT,
                 cache_dir: Path | None = CACHE_DIR,
                 peaks: dict | None = None):
        self.workload, self.seed = workload, seed
        self.bench = load_benchmark(root)
        self.wl, conf, self.mod, traffic = resolve(self.bench, workload, root)
        self.conf = {**conf, **(conf_update or {})}
        self.traffic = {**traffic, **(traffic_update or {})}
        if self.traffic["loop"] not in ("open", "closed"):
            raise CellError(f"unknown loop {self.traffic['loop']!r} in "
                            f"traffic {self.wl['traffic']!r}")
        import jax

        self.device = device_report(jax, int(self.wl["chips"]), require_tpu)
        self.jax = configure_jax(cache_dir)
        self.trace_dir = (Path(cache_dir).parent if cache_dir is not None
                          else ROOT / ".bench_cache") / "trace"
        self.clock = CompileClock()
        self.peaks = peaks if peaks is not None else \
            load_peaks(self.device["kind"])
        if self.peaks is None:
            raise CellError(f"{self.device['kind']!r} is not in "
                            "bench/peaks.json")
        self.cell = self.mod.build(self.conf, CODEBOOK_SEED)
        self.perturb = float(self.traffic["perturb"])

    def requests(self, count: int, seed: int | None = None,
                 pool_seed: int = POOL_SEED) -> list:
        """The run's requests: the cell's fixed pool of ``count`` requests,
        the same set in every run, in an order drawn from the seed."""
        pool = self.cell.make_requests(pool_seed, count, self.perturb)
        seed = self.seed if seed is None else seed
        order = np.random.default_rng(abs(int(seed))).permutation(count)
        return [pool[i] for i in order]

    def start(self, rec=None):
        """A started ``Runtime`` serving the cell's engine, warmed by one
        request through fill, sweep burst, retire, decode and postprocess."""
        from repro.runtime import FailurePolicy, Runtime

        cell = self.cell
        rt = Runtime(watchdog_s=WATCHDOG_S, **({"obs": rec} if rec else {}))
        # The cadenced corruption probe (Engine.health_check) compiles one
        # gather per live-row count, so it would compile inside the window;
        # it is off (PERF.md, Open questions).
        rt.register(cell.name, cell.engine,
                    failure=FailurePolicy(health_check_every=0))
        rt.start()
        warm = cell.make_requests(WARM_SEED, 1, self.perturb)
        try:
            gid = rt.submit(cell.name, warm[0][0], **warm[0][1])
            cell.record(rt.result(gid, timeout=WATCHDOG_S))
        except BaseException:
            rt.stop()
            raise
        return rt


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, grace_s: float = GRACE_S,
             **session_kw) -> dict:
    """Run one cell end to end and return its result line as a dict.

    ``session_kw`` (``require_tpu``, ``conf_update``, ``traffic_update``,
    ``cache_dir``, ``peaks``) lets the tests run a cell at a size the CPU
    holds; the benchmark's own runs never pass them.
    """
    from repro import obs as obs_mod

    ses = Session(workload, seed, **session_kw)
    cell, traffic, jax = ses.cell, ses.traffic, ses.jax
    bench, wl, device, clock = ses.bench, ses.wl, ses.device, ses.clock
    loop = traffic["loop"]
    if loop == "open":
        from bench.load import open_loop_times
        times = open_loop_times(float(traffic["rate_per_s"]), seconds, seed)
        requests = ses.requests(len(times))
    else:
        requests = ses.requests(int(traffic["pool"]))

    rec = obs_mod.Recorder() if trace else None
    rt = ses.start(rec)
    try:
        set_compiles, set_compile_s, set_loaded = clock.snapshot()
        set_traced = clock.traced
        t0 = CLOCK() + 0.05
        setup_s = t0 - t_start
        t_end = t0 + seconds
        deadline = t_end + grace_s
        prof = (Profiler(jax, cell.engine, t_end, min(TRACE_TAIL_S, seconds),
                         ses.trace_dir) if trace else None)
        from bench import load
        pauses = GcPauses()
        if loop == "open":
            out = load.run_open(rt, cell.name, requests, times, t0, deadline)
        else:
            out = load.run_closed(rt, cell.name, requests,
                                  int(traffic["outstanding"]), t0, t_end,
                                  deadline)
        gc_line = pauses.close()
        if prof is not None:
            prof.join()
            prof.engine = None
        win_compiles, _, win_loaded = clock.snapshot()
        win_compiles -= set_compiles
        win_loaded -= set_loaded
        win_traced = clock.traced - set_traced
        win_names = sorted({name for name, _ in
                            clock.requests[set_compiles + set_loaded:]})
    finally:
        rt.stop()
    mem_peak = peak_memory(jax)
    del rt  # the program's state goes before the reference runs
    cell.release()

    # -- what the load generator saw ---------------------------------------
    n = len(out.due)
    failed = sum(1 for i in range(n) if out.answer[i] is None)
    lat = [out.done[i] - out.due[i] for i in range(n)
           if out.answer[i] is not None]
    in_window = sum(1 for i in range(n)
                    if out.answer[i] is not None and out.done[i] <= t_end)
    late = np.asarray(out.lateness) * 1e3 if out.lateness else np.zeros(1)
    slowest = sorted(clock.requests[:set_compiles + set_loaded],
                     key=lambda r: -r[1])[:3]
    log(f"[{workload}] set-up {setup_s:.3f} s: {set_compiles} programs "
        f"compiled, {set_loaded} loaded from the compile cache, "
        f"{set_compile_s:.3f} s in all; slowest "
        + ", ".join(f"{n} {d:.3f} s" for n, d in slowest))
    log(f"[{workload}] window {seconds} s, {loop} loop: {n} requests sent, "
        f"{n - failed} answered, {failed} failed, {in_window} answered "
        f"inside the window; compiles inside the window: {win_compiles}"
        + (f" {win_names}" if win_names else "")
        + f", programs loaded from the compile cache there: {win_loaded}, "
        f"functions traced there: {win_traced}")
    if out.lateness:
        log(f"[{workload}] generator lateness ms: p50 "
            f"{np.percentile(late, 50):.4f} p99 {np.percentile(late, 99):.4f}"
            f" max {late.max():.4f} (due {out.due[int(late.argmax())] - t0:.3f}"
            f" s into the window, which opened at {t0:.3f} s on the "
            "monotonic clock)")
    log(f"[{workload}] while the load ran: {gc_line}; at most {out.threads} "
        "threads")
    if failed:
        errs = sorted({e for e in out.error if e})
        log(f"[{workload}] failures: {errs[:3]}")

    result: dict = {"correct": False, "attempted": n, "failed": failed,
                    "metrics": {}, "device": dict(device)}
    result["device"]["memory_peak_bytes"] = mem_peak

    # -- metrics -----------------------------------------------------------
    if not trace:
        for m in cell_metrics(bench, workload, "end_to_end"):
            if not lat and m["name"] != "setup_s":
                continue
            result["metrics"][m["name"]] = {
                "value": end_to_end(m["name"], lat, in_window, seconds,
                                    setup_s),
                "unit": m["unit"]}
    else:
        from bench import trace as tr
        tdata = tr.load_xplane(str(ses.trace_dir))
        marks = prof.marks
        anchor = tdata["anchor_ns"]
        if anchor is None:
            raise CellError("the trace holds no anchor annotation")
        host0 = marks["host0"]
        lo, hi = anchor, anchor + (marks["host1"] - host0) * 1e9
        spans = rec.spans.snapshot()
        planes = tr.device_planes(tdata)
        window_s = (hi - lo) * 1e-9
        busy = tr.busy_share(tdata, lo, hi)
        result["device"]["busy_s"] = busy * window_s
        result["device"]["window_s"] = window_s
        # spans and answers are read over the whole window, the device
        # trace over its profiled part
        done_in = [i for i in range(n) if out.answer[i] is not None
                   and t0 <= out.done[i] <= t_end]
        ctx = types.SimpleNamespace(
            trace=tdata, lo=lo, hi=hi, planes=planes, window_s=window_s,
            host0=t0, host1=t_end, spans=spans, engine=cell.name,
            sweeps=marks["sweeps1"] - marks["sweeps0"], slots=cell.slots,
            sweep_work=cell.sweep_work, row_flops=cell.row_flops,
            row_sweeps=sum(cell.sweeps(out.answer[i]) for i in done_in),
            peaks=ses.peaks, chips=int(wl["chips"]))
        result["metrics"] = per_layer(cell_metrics(bench, workload,
                                                   "per_layer"), ctx)
        host_spans = [(sp.track, sp.name, sp.t0, sp.t1) for sp in spans
                      if sp.t1 is not None and not sp.instant
                      and sp.track != "requests"]
        ops = [(s, e) for p in planes[:1]
               for _, s, e in tr.op_events(tdata, p)]
        gaps = tr.idle_gaps(ops, lo, hi)
        result["breakdown"] = {
            "device_ops": tr.top_ops(tdata, lo, hi),
            "idle_gaps": tr.label_gaps(
                gaps, host_spans, lambda t: anchor + (t - host0) * 1e9)}
        log(f"[{workload}] traced {window_s:.3f} s: device busy "
            f"{busy * 100:.3f}%, {ctx.sweeps} sweeps; {len(done_in)} "
            f"answers in the {seconds} s window")
        shutil.rmtree(ses.trace_dir, ignore_errors=True)

    # -- correct: every answer of the window against the plain reference ---
    served = [(out.index[i], out.answer[i]) for i in range(n)
              if out.answer[i] is not None]
    t_ref = CLOCK()
    nums = check_answers(cell, requests, served)
    log(f"[{workload}] reference compared {len(served)} answers in "
        f"{CLOCK() - t_ref:.3f} s")
    for k, v in nums.items():
        if k not in ses.mod.LIMITS:
            log(f"reading {k}: {v!r}")
    checks = limited(ses.mod, nums)
    ok = failed == 0 and n > 0 and all(
        math.isfinite(v) and v <= lim for v, lim in checks.values())
    result["correct"] = bool(ok)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} limit {lim!r}")
    return result


def check_answers(cell, requests: list, served: list) -> dict:
    """Compare what the served path answered with the plain reference over
    the same requests.  A request served twice (closed loops cycle through
    their pool) is compared each time."""
    idx = sorted({i for i, _ in served})
    pos = {i: k for k, i in enumerate(idx)}
    want = cell.reference([requests[i] for i in idx])
    got = [cell.record(ans) for _, ans in served]
    return cell.compare(got, [want[pos[i]] for i, _ in served])


def limited(mod, nums: dict) -> dict:
    """The compared numbers that decide ``correct``, each with its limit
    (``LIMITS`` of the configuration's module)."""
    return {k: (nums[k], lim) for k, lim in mod.LIMITS.items()}
