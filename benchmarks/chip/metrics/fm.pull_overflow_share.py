"""Share of FM moves whose pulled set took more than one scatter round
of the move loop's update (more than ``PULL_K`` pulled slots,
``kernels/fm_fused.py``): ``pull_overflow`` over ``lane_iters`` of the
window's ``fm`` launches (``core/fm.py``)."""


def read(run):
    launches = [p for _, kind, p in run.events
                if kind == "launch" and p["kind"] == "fm"]
    if any("pull_overflow" not in p or "lane_iters" not in p
           for p in launches):
        return None
    moves = sum(p["lane_iters"] for p in launches)
    if not moves:
        return None
    return 100.0 * sum(p["pull_overflow"] for p in launches) / moves
