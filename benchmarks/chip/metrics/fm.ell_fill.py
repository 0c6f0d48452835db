"""Share of the FM neighbour tables' slots that hold a neighbour: the
``slots`` of the window's ``fm`` launches over their real ``lanes``
times ``n_pad`` times ``d_pad`` (``bucket[:2]``, ``core/fm.py``)."""


def read(run):
    launches = [p for _, kind, p in run.events
                if kind == "launch" and p["kind"] == "fm"]
    if any("slots" not in p for p in launches):
        return None
    table = sum(p["lanes"] * p["bucket"][0] * p["bucket"][1]
                for p in launches)
    if not table:
        return None
    return 100.0 * sum(p["slots"] for p in launches) / table
