"""Share of FM moves made after each pass's last improvement, which the
revert to the best state throws away: ``iters_after_best`` over
``lane_iters`` of the window's ``fm`` launches (``core/fm.py``)."""


def read(run):
    launches = [p for _, kind, p in run.events
                if kind == "launch" and p["kind"] == "fm"]
    if any("iters_after_best" not in p or "lane_iters" not in p
           for p in launches):
        return None
    moves = sum(p["lane_iters"] for p in launches)
    if not moves:
        return None
    return 100.0 * sum(p["iters_after_best"] for p in launches) / moves
