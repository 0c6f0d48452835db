"""Share of the FM lanes whose neighbour table left out at least one
side row (a band anchor carried beside the table, ``core/fm.py``):
``side_lanes`` over ``lanes`` of the window's ``fm`` launches."""


def read(run):
    launches = [p for _, kind, p in run.events
                if kind == "launch" and p["kind"] == "fm"]
    if any("side_lanes" not in p for p in launches):
        return None
    lanes = sum(p["lanes"] for p in launches)
    if not lanes:
        return None
    return 100.0 * sum(p["side_lanes"] for p in launches) / lanes
