"""Chip benchmark of the ordering service: runs one cell once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine whose TPU chips the cell
asks for; without them it exits non-zero and prints no result.  The
cell, its configuration, its traffic mix and its per-layer metrics are
found by name from ``BENCHMARK.json`` (``spec.py``).

A run:

1. Set-up (``setup_s``, from process start): the device check, JAX's
   persistent compile cache in ``<checkout>/.jax_cache``, the request
   pool (the configuration's fixed set of ``set_size`` graphs, in an
   order made from ``--seed``), and the warm-up.  The warm-up drives the
   window's own traffic over the pool, from its start, on a service of
   its own: the service builds executables per shape and keys its BFS and
   matching ones on exact lane counts, so only the graphs the window will
   order warm every executable it will use.  It stops once it has
   ordered ``HEADROOM`` times what the window would at the rate of its
   rounds that built no new executable, plus a traced run's tail.  Set-up
   has a budget: what the run's limit leaves once the window, the late
   wait, the traced tail and the check have had their room
   (``setup_budget``).  A warm-up that passes it ends the run at once
   with ``OVER_BUDGET`` and the reason, and prints no result.  At the
   end of set-up the run logs the lattice of executables the warm-up
   used (``lattice``).
2. The window: a fresh ``OrderingService``, the traffic's closed loop for
   ``--seconds``; requests in flight at the close are waited for under
   the same load.  ``--trace 1`` records the service's spans and events
   over the window, at its own pace, and then profiles a tail of one
   round of answers (``clients`` orderings from a request boundary) with
   the spans annotated on the device; it reports the per-layer metrics
   instead of the end-to-end ones.
3. The check (``check.py``), once the service is freed: every window
   request answered with a permutation, and the worst fill ratio and
   top-separator imbalance of a sample against the plain reference
   within the configuration's limits.

The last line of standard output is the result as one JSON object.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import check as check_mod  # noqa: E402
import devtrace  # noqa: E402
import kernel_bytes  # noqa: E402
import loop  # noqa: E402
import pool as pool_mod  # noqa: E402
import spec  # noqa: E402

#: the warm-up orders this many times the graphs the window is expected
#: to order at the warm-up's steady rate
HEADROOM = 1.5
#: the time a run may take in all, and the time the first run of a cell
#: in a checkout may take, which compiles (the benchmark's run limits,
#: which hold per cell: PERF.md section 2)
RUN_LIMIT_S = 360.0
FIRST_RUN_LIMIT_S = 1200.0
#: kept for the check, which orders a sample of 6 with the plain
#: reference after the window: 2.3-2.4 s each at 13^3, up to 7 s each
#: on circuits of 2-4k vertices
CHECK_RESERVE_S = 45.0
#: the exit code of a run whose set-up passed its budget
OVER_BUDGET = 5
#: the launch kinds whose executables the set-up's lattice lists
LATTICE_KINDS = ("fm", "bfs", "match")
_T_IMPORT = time.perf_counter()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_age() -> float:
    """Seconds since this process started (``/proc``), or since this
    module was imported where ``/proc`` is not there."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


def dir_bytes(path: str) -> int:
    total = 0
    for d, _, files in os.walk(path):
        for name in files:
            with contextlib.suppress(OSError):
                total += os.path.getsize(os.path.join(d, name))
    return total


class Events:
    """Event-bus collector: ``stage`` and ``launch`` events with the
    host time at which they arrived."""

    def __init__(self):
        self.events: List[tuple] = []

    def on_event(self, kind: str, payload: dict) -> None:
        if kind in ("stage", "launch"):
            self.events.append((time.perf_counter(), kind, dict(payload)))

    def between(self, t0: float, t1: float) -> List[tuple]:
        return [e for e in self.events if t0 <= e[0] <= t1]


class CompileLog:
    """JAX's persistent-cache hits and misses, with their host times."""

    def __init__(self):
        self.events: List[tuple] = []

    def on_event(self, event: str, **kw) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events.append((time.perf_counter(), event))

    def classify(self, t0: float, t1: float) -> str:
        kinds = {e for t, e in self.events if t0 <= t <= t1}
        if "/jax/compilation_cache/cache_misses" in kinds:
            return "compiled"
        if "/jax/compilation_cache/cache_hits" in kinds:
            return "loaded"
        return "traced"


def first_use_report(events: Events, clog: CompileLog, t0: float,
                     t1: float) -> dict:
    """First uses between ``t0`` and ``t1``, split by what JAX did in
    them: built by the compiler, loaded from the persistent cache, or
    neither (traced only: the program was already in memory or was too
    quick to be cached)."""
    out = {}
    for t, kind, p in events.between(t0, t1):
        if kind == "stage" and p["compile"]:
            how = clog.classify(t - p["seconds"], t)
            s = out.setdefault(how, [0, 0.0])
            s[0] += 1
            s[1] += p["seconds"]
    return out


def first_uses_text(fu: dict) -> str:
    """``first_use_report``'s counts on one line."""
    return ", ".join(f"{how} {count} ({secs:.3f} s, {secs / count:.4f} s "
                     f"each)" for how, (count, secs) in sorted(fu.items())) \
        or "none"


def lattice(events) -> dict:
    """Per launch kind of ``LATTICE_KINDS``, the executables the
    ``launch`` events among ``events`` used, as ``{(n_pad, d_pad):
    {lanes_pad: set of executable keys}}``.  An executable is keyed, as
    the program's own first-use keys are, by its bucket, its rounds (FM
    passes, BFS width, matching rounds) and its padded lane count."""
    out = {k: {} for k in LATTICE_KINDS}
    for _, kind, p in events:
        if kind == "launch" and p["kind"] in out:
            b = tuple(p["bucket"])
            out[p["kind"]].setdefault(b[:2], {}).setdefault(
                p["lanes_pad"], set()).add((b, p["rounds"]))
    return out


def log_lattice(events) -> None:
    """One line per launch kind: how many executables, and the distinct
    ``lanes_pad`` of each ``(n_pad, d_pad)`` bucket."""
    for kind, buckets in lattice(events).items():
        count = sum(len(keys) for pads in buckets.values()
                    for keys in pads.values())
        pads = "; ".join(f"{n}x{d}: " + ",".join(map(str, sorted(lp)))
                         for (n, d), lp in sorted(buckets.items()))
        log(f"lattice {kind}: {count} executables; lanes_pad by n_pad x "
            f"d_pad: {pads or '-'}")


def setup_budget(limit: float, seconds: float, round_s) -> float:
    """The set-up seconds a run may spend within ``limit``: what is left
    once the window (``seconds``), the late wait and the traced tail
    (twice the longest steady round ``round_s``, each at most
    ``loop.LATE_S``, and ``LATE_S`` each while no round was steady) and
    the check (``CHECK_RESERVE_S``) have had their room."""
    late = loop.LATE_S if round_s is None else min(round_s, loop.LATE_S)
    return limit - seconds - 2 * late - CHECK_RESERVE_S


class OverBudget(Exception):
    """The warm-up passed the set-up budget."""

    def __init__(self, age: float, budget: float, done: int):
        super().__init__(f"set-up {age:.3f} s passed its budget of "
                         f"{budget:.3f} s")
        self.age, self.done = age, done


class Graphs:
    """The program's graph of each pool request, made on first use and
    kept, so the window orders the objects the warm-up made."""

    def __init__(self, pool, make):
        self.pool, self.make, self.made = pool, make, {}

    def __getitem__(self, i: int):
        if i not in self.made:
            self.made[i] = self.make(self.pool[i].n, self.pool[i].edges)
        return self.made[i]


class WarmUp:
    """The warm-up's ``close``: true once the answers cover ``HEADROOM``
    times what the window would order at the rate of the rounds (of
    ``clients`` answers) that built no new executable, plus ``extra``
    rounds (a traced run's tail).  The window orders the pool from its
    start, so the graphs it will order are then warmed."""

    def __init__(self, events, clients: int, seconds: float, extra: int):
        self.events, self.clients = events, clients
        self.seconds, self.extra = seconds, extra
        self.mark = (0.0, 0, 0)             # time, answers, first uses
        self.steady_s, self.steady_n = 0.0, 0
        self.longest = None                 # the longest steady round, s
        self.need = None

    def firsts(self) -> int:
        return sum(1 for _, k, p in self.events.events
                   if k == "stage" and p["compile"])

    def __call__(self, t: float, done: int) -> bool:
        t0, d0, f0 = self.mark
        if done - d0 < self.clients:
            return False
        f = self.firsts()
        self.mark = (t, done, f)
        if f == f0:
            self.longest = max(self.longest or 0.0, t - t0)
            self.steady_s += t - t0
            self.steady_n += done - d0
            self.need = self.clients * self.extra + math.ceil(
                HEADROOM * self.seconds * self.steady_n / self.steady_s)
        return self.need is not None and done >= self.need


class RunView:
    """What a per-layer metric reader (``metrics/<name>.py``) reads."""

    def __init__(self, result, events, spans, trace, trace_events, peaks):
        self.t_open, self.t_close = result.t_open, result.t_close
        self.seconds = result.t_close - result.t_open
        self.requests = [r for r in result.records if r.counted]
        self.completed = [r for r in self.requests if r.status == "ok"
                          and r.t_resolve is not None
                          and r.t_resolve <= result.t_close]
        self.events = events
        self.spans = spans
        self.trace = trace
        self.trace_events = trace_events
        self.peaks = peaks


def end_to_end(view: RunView, setup_s: float) -> dict:
    """The end-to-end metrics: set-up, and the rate over all the work
    and all the time of the window."""
    return {"setup_s": setup_s,
            "vertices_per_s": sum(r.n for r in view.completed)
            / view.seconds}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        with contextlib.suppress(Exception):
            peak = max(peak, int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def first_run(cache_dir: str, cell: str) -> bool:
    """Whether this is the first run of ``cell`` with ``cache_dir``: no
    run of the cell has left its mark there.  Leaves the mark at the
    run's start, so that every later run of the cell there is held to
    the shorter limit, as a run limit per cell holds it, however this
    run ends.  The mark lives in the compile cache, so a cache emptied
    makes the next run a first one again."""
    mark = os.path.join(cache_dir, "chipbench", cell)
    first = not os.path.exists(mark)
    os.makedirs(os.path.dirname(mark), exist_ok=True)
    open(mark, "a").close()
    return first


def main(argv=None, root: str = ROOT, require_chip: bool = True) -> int:
    """Run one cell; returns the exit code.  ``require_chip=False``
    skips the look for a TPU and leaves the process's compile cache as
    it is (the tests drive the rest of a run on the CPU with it)."""
    args = parse(argv)
    try:
        cell = spec.load_cell(root, args.workload)
    except spec.SpecError as e:
        log(f"error: {e}")
        return 2
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"error: the program is not in this checkout ({src})")
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)
    cache_dir = os.path.join(root, ".jax_cache")
    if require_chip:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell.chips):
        log(f"error: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"found {len(devs)} {devs[0].platform} device(s)")
        return 3
    peaks = kernel_bytes.peaks(devs[0].device_kind) if require_chip else \
        {kernel_bytes.FM_BOUND: 1.0}

    from repro import obs, util
    from repro.core.band import bfs_mode_default
    from repro.core.fm import gain_mode_default
    from repro.core.graph import Graph
    from repro.kernels.ops import fm_mode_default
    from repro.service import OrderingService

    if require_chip:
        util.enable_compile_cache()
    first = first_run(cache_dir, cell.name)
    limit = FIRST_RUN_LIMIT_S if first else RUN_LIMIT_S
    log(f"cache: {cache_dir} holds {dir_bytes(cache_dir)} bytes at start; "
        f"{'the first' if first else 'a later'} run of {cell.name} with "
        f"it, so the run's limit is {limit:.0f} s")
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"paths fm={fm_mode_default()} gain={gain_mode_default()} "
        f"bfs={bfs_mode_default()}")

    cfg, traffic = cell.config, cell.traffic
    clients, nproc = int(traffic["clients"]), int(cfg["nproc"])
    pool = pool_mod.build_pool(cfg, args.seed)
    graphs = Graphs(pool, Graph.from_edges)

    events, clog = Events(), CompileLog()
    obs.register_collector(events)
    jax.monitoring.register_event_listener(clog.on_event)
    try:
        t_warm = time.perf_counter()
        warm_close = WarmUp(events, clients, args.seconds, args.trace)
        step = {"next": 0, "tightest": 0.0}

        def progress(t: float, done: int) -> None:
            age = process_age()
            budget = setup_budget(limit, args.seconds, warm_close.longest)
            if age > budget:
                raise OverBudget(age, budget, done)
            step["tightest"] = max(step["tightest"], age / budget)
            if done >= step["next"]:
                step["next"] = done + max(clients, 5)
                log(f"warm-up: {done} answered, {t - t_warm:.1f} s, "
                    f"{warm_close.firsts()} first uses so far, set-up "
                    f"{age:.1f} s of a budget of {budget:.1f} s")

        try:
            warm = loop.closed_loop(OrderingService(), pool, graphs,
                                    clients, nproc, close=warm_close,
                                    on_pump=progress)
        except OverBudget as over:
            fu = first_use_report(events, clog, t_warm, time.perf_counter())
            log_lattice(events.events)
            log(f"error: {over}, before the window could open; the run's "
                f"limit is {limit:.0f} s")
            log(f"set-up seconds: {over.age:.3f}")
            log(f"first uses so far: {first_uses_text(fu)}")
            log(f"answers so far: {over.done}")
            log("steady round: " + (
                "none" if warm_close.longest is None else
                f"yes, the longest {warm_close.longest:.3f} s"))
            return OVER_BUDGET
        fu = first_use_report(events, clog, t_warm, time.perf_counter())
        log(f"warm-up: {len(warm.records)} requests, "
            f"{sum(r.status == 'ok' for r in warm.records)} answered, "
            f"{time.perf_counter() - t_warm:.3f} s, steady rate "
            f"{warm_close.steady_n / max(warm_close.steady_s, 1e-9):.4f} "
            f"orderings/s, aimed at {warm_close.need}")
        log(f"first uses in set-up: {first_uses_text(fu)}")
        log_lattice(events.events)
        log(f"set-up budget: {process_age():.3f} s of "
            f"{setup_budget(limit, args.seconds, warm_close.longest):.3f} s "
            f"at the warm-up's end; at most {100 * step['tightest']:.2f}% "
            f"of it used at any pump")
        if warm_close.need is None:
            log("warning: the warm-up ended before a round built nothing "
                "new; the window may meet first uses")
        del warm
        gc.collect()

        svc = OrderingService()
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        prof = {"stack": contextlib.ExitStack()}

        def on_tail(what: str, t: float) -> None:
            # the profiler and the spans' device annotations are on only
            # in the tail, so the window runs at its untraced pace
            if what == "start":
                jax.profiler.start_trace(trace_dir)
                prof["stack"].enter_context(
                    obs.tracing(annotate_device=True))
                prof["stack"].enter_context(
                    jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN))
            else:
                prof["stack"].close()
                jax.profiler.stop_trace()

        with (obs.tracing() if args.trace else
              contextlib.nullcontext()) as tracer:
            setup_s = process_age()
            result = loop.closed_loop(
                svc, pool, graphs, clients, nproc,
                close=lambda t, done: t >= args.seconds,
                tail=clients if args.trace else 0, on_tail=on_tail)
        dev = device_info(jax, cell.chips)
        spans = list(tracer.spans) if args.trace else None
        del svc, tracer
        gc.collect()
    finally:
        obs.unregister_collector(events)
        jax.monitoring.unregister_event_listener(clog.on_event)

    if result.starved:
        log(f"error: the window used up all {len(pool)} pool graphs")
        return 4
    window = events.between(result.t_open, result.t_close)
    log(f"window: {result.t_close - result.t_open:.3f} s, "
        f"{sum(r.counted for r in result.records)} requests submitted, "
        f"{result.submitted} of {len(pool)} pool graphs used, "
        f"{len(graphs.made)} graphs warmed")
    fu = first_use_report(events, clog, result.t_open, result.t_end)
    log(f"first uses in the window and its wait: "
        f"{sum(c for c, _ in fu.values())} {json.dumps(fu)}")

    view = RunView(result, window, spans, None, [], peaks)
    counted = view.requests
    metrics = {}
    breakdown = None
    if args.trace:
        reduced = None
        if result.tail is not None:
            t_read = time.perf_counter()
            xspace = devtrace.find_xspace(trace_dir)
            reduced = devtrace.reduce_trace(devtrace.read_xspace(xspace))
            log(f"trace: tail {result.tail[1] - result.tail[0]:.3f} s, "
                f"{os.path.getsize(xspace)} bytes, read in "
                f"{time.perf_counter() - t_read:.3f} s")
            view.trace_events = events.between(*result.tail)
        view.trace = reduced
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            breakdown = reduced["breakdown"]
        for m in cell.per_layer:
            v = m.read(view)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
    else:
        values = end_to_end(view, setup_s)
        for m in cell.end_to_end:
            metrics[m.name] = {"value": values[m.name], "unit": m.unit}
    shutil.rmtree(trace_dir, ignore_errors=True)

    t_check = time.perf_counter()
    correct, numbers = check_mod.check(result.records, pool, args.seed,
                                       cfg["check"])
    log(f"check: {time.perf_counter() - t_check:.3f} s")
    out = {"correct": bool(correct), "attempted": len(counted),
           "failed": sum(1 for r in counted if r.status != "ok"),
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = numbers
    for name, (value, limit) in numbers.items():
        log(f"check {name}: {value} (limit {limit})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
