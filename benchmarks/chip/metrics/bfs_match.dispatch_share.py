"""Host time blocked in steady band-BFS and matching dispatches over the
window (``core/band.py``, ``core/coarsen.py``)."""


def read(run):
    s = sum(p["seconds"] for _, kind, p in run.events
            if kind == "stage" and p["name"] in ("bfs", "match")
            and not p["compile"])
    return 100.0 * s / run.seconds if s > 0 else None
