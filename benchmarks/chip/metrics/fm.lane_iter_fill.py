"""Share of the FM move loop's lane iterations that moved a vertex: the
``lane_iters`` of the window's ``fm`` launches over their ``lanes_pad``
times ``trips`` (``core/fm.py``).  The vmapped loop runs until its
slowest lane stops; the rest idle through its iterations."""


def read(run):
    launches = [p for _, kind, p in run.events
                if kind == "launch" and p["kind"] == "fm"]
    if any("trips" not in p or "lane_iters" not in p for p in launches):
        return None
    ran = sum(p["lanes_pad"] * p["trips"] for p in launches)
    if not ran:
        return None
    return 100.0 * sum(p["lane_iters"] for p in launches) / ran
