"""The one closed-loop traffic driver that every traffic mix feeds.

A traffic file gives ``clients``: each client submits its next graph as
soon as its last one resolves.  The driver runs in one thread and calls
the service's own ``submit`` / ``pump``; with no deadlines and one size
class the service's schedule is then a function of the submissions alone,
so the same pool driven twice makes the same waves.

Requests submitted before the close are the window's.  After the close
the clients keep submitting, so the window's last requests finish under
the load they started in; the window ends once every window request has
resolved (or ``LATE_S`` has passed).  A run may then ask for a tail: the
driver goes on, still in a closed loop, until ``tail`` more answers have
come, and calls ``on_tail`` as the tail starts (a request boundary: no
pump has run since the last window request resolved) and as it ends.
A client whose pool has run out stops; a window that ran it out before
its close has failed.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional


#: how long the driver waits past the close for the window's requests
LATE_S = 60.0


@dataclasses.dataclass
class Record:
    index: int                          # position in the pool
    client: int
    n: int
    t_submit: float
    counted: bool                       # submitted before the close
    t_resolve: Optional[float] = None
    result: object = None               # the service's ``OrderResult``

    @property
    def status(self) -> str:
        return self.result.status if self.result is not None else ""

    @property
    def perm(self):
        return self.result.perm if self.result is not None else None


@dataclasses.dataclass
class LoopResult:
    records: List[Record]
    t_open: float
    t_close: float
    t_end: float                        # the window's last request resolved
    submitted: int                      # pool entries used
    starved: bool = False               # the pool ran out before the close
    tail: Optional[tuple] = None        # (t0, t1) of the tail, if one ran


def closed_loop(svc, pool, graphs, clients: int, nproc: int,
                close: Callable[[float, int], bool],
                on_pump: Callable[[float, int], None] = lambda t, done: None,
                tail: int = 0,
                on_tail: Callable[[str, float], None] = lambda what, t: None,
                ) -> LoopResult:
    """Drive ``clients`` closed-loop clients over ``pool`` in order.

    ``close(t, completed)`` says when the window closes; ``graphs[i]``
    is the program's graph for ``pool[i]``.  ``tail`` answers more are
    driven after the window, with ``on_tail("start" | "end", t)``.
    """
    outstanding = {}
    records: List[Record] = []
    state = {"next": 0, "closed": False, "starved": False}

    def submit(client: int) -> None:
        if state["next"] >= len(pool):
            # the client stops; before the close the run has failed
            state["starved"] = state["starved"] or not state["closed"]
            return
        req = pool[state["next"]]
        state["next"] += 1
        rec = Record(req.index, client, req.n, time.perf_counter(),
                     not state["closed"])
        rid = svc.submit(graphs[req.index], seed=req.seed, nproc=nproc)
        if svc.poll(rid) is not None:
            raise RuntimeError(f"pool graph {req.index} resolved at "
                               "submit: a repeated request")
        outstanding[rid] = rec
        records.append(rec)

    t_open = time.perf_counter()
    for c in range(clients):
        submit(c)
    completed = 0
    t_close = t_end = t_tail = None
    tail_done = 0
    while True:
        resolved = svc.pump()
        t = time.perf_counter()
        answered = []
        for rid, res in sorted(resolved.items()):
            rec = outstanding.pop(rid)
            rec.t_resolve = t
            rec.result = res
            answered.append(rec)
        completed += len(answered)
        if t_tail is not None:
            tail_done += len(answered)
        # the window closes on a pump that answered a request, so its
        # time ends with the work it counts; what the answered clients
        # submit next is after the close
        if t_close is None and answered and close(t - t_open, completed):
            state["closed"] = True
            t_close = t
        for rec in answered:
            submit(rec.client)
        on_pump(t, completed)
        # answers the service no longer holds will never come
        lost = not outstanding or (not resolved
                                   and svc.queue_depth() == 0)
        if t_close is None and lost:
            state["closed"] = True
            t_close = t
        if t_close is not None and t_end is None and (
                lost or t - t_close > LATE_S
                or not any(r.counted for r in outstanding.values())):
            t_end = t
            if tail <= 0 or lost:
                return LoopResult(records, t_open, t_close, t_end,
                                  state["next"], state["starved"])
            t_tail = t
            on_tail("start", t)
        elif t_tail is not None and (tail_done >= tail or lost
                                     or t - t_tail > LATE_S):
            on_tail("end", t)
            return LoopResult(records, t_open, t_close, t_end,
                              state["next"], state["starved"], (t_tail, t))
