"""From a profiler trace to the device's busy time, its idle gaps and the
time of each program.

``read_xspace`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into a small dict (``compact``), which ``reduce_trace`` reduces; the
tests check the reduction on a small recorded trace kept beside them.

The compact form, all times in nanoseconds on the trace's one clock:

* ``window``: ``[t0, t1]`` of the ``bench:traced`` host annotation that
  marks the traced tail;
* ``ops``: ``[name, start, duration, device]`` of every operation on a
  device (the ``XLA Ops`` line of each device plane);
* ``modules``: the same for whole programs (the ``XLA Modules`` line);
* ``host``: the host annotations of the service and the benchmark
  (``sched:pump``, ``router:wave``, ``dispatch:*``, ``stage:*``,
  ``bench:*``), so an idle gap can be named by what the host was doing.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional

WINDOW_SPAN = "bench:traced"
_HOST = re.compile(r"^(bench|sched|router|dispatch|stage|recover|fault):"
                   r"|^drain$")
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def find_xspace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xspace(path: str) -> dict:
    """The compact form of one profiler trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"window": None, "ops": [], "modules": [], "host": [],
           "devices": []}
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = len(out["devices"])
            out["devices"].append(plane.name)
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                out[key].extend([ev.name, float(ev.start_ns),
                                 float(ev.duration_ns), dev]
                                for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        out["window"] = [float(ev.start_ns),
                                         float(ev.start_ns + ev.duration_ns)]
                    elif _HOST.match(ev.name):
                        out["host"].append([ev.name, float(ev.start_ns),
                                            float(ev.duration_ns)])
    return out


def _clip(events, t0: float, t1: float, dev=None):
    for name, s, d, *where in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a and (dev is None or where == [dev]):
            yield name, a, b


def _union(intervals) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _innermost(host, starts, t: float) -> str:
    """Name of the innermost host annotation open at ``t``, or none:
    the latest-starting one that holds ``t`` (``host`` sorted by start,
    ``starts`` its start times)."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        name, s, d = host[i]
        if t < s + d:
            return name
    return "none"


def reduce_trace(tr: dict, top: int = 10) -> Optional[dict]:
    """Busy and idle time of the traced window, averaged over the device
    planes, with the programs' times and a breakdown (idle gaps of the
    first device); None when the trace holds no window or no device
    operation."""
    if not tr.get("window") or not tr.get("ops"):
        return None
    t0, t1 = tr["window"]
    window_s = (t1 - t0) * 1e-9
    ndev = max(len(tr.get("devices") or ()), 1)
    busy_by_dev = [_union((a, b) for _, a, b in _clip(tr["ops"], t0, t1, d))
                   for d in range(ndev)]
    busy_s = sum(b - a for u in busy_by_dev for a, b in u) * 1e-9 / ndev
    busy = busy_by_dev[0]
    by_op: Dict[str, float] = defaultdict(float)
    for name, a, b in _clip(tr["ops"], t0, t1):
        by_op[name] += (b - a) * 1e-9
    by_module: Dict[str, float] = defaultdict(float)
    for name, a, b in _clip(tr["modules"], t0, t1):
        by_module[name] += (b - a) * 1e-9
    gaps: Dict[str, float] = defaultdict(float)
    host = sorted(tr["host"], key=lambda h: h[1])
    starts = [h[1] for h in host]
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps[_innermost(host, starts, (a + b) / 2)] += (b - a) * 1e-9
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": busy_s,
            "modules": dict(by_module),
            "breakdown": {"device_ops": [list(kv) for kv in rank(by_op)],
                          "idle_gaps": [list(kv) for kv in rank(gaps)]}}


def module_seconds(reduced: dict, program: str) -> float:
    """Summed device time of the programs whose name holds ``program``."""
    return sum(s for name, s in reduced["modules"].items()
               if program in name)
