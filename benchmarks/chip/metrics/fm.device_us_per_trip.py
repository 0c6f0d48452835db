"""Device time of the FM program (``fm_refine_multi``) per serial
iteration of its move loop, in the traced tail: its device time in the
trace over the ``trips`` of the tail's ``fm`` launches
(``core/fm.py``)."""


def read(run):
    if run.trace is None:
        return None
    from devtrace import module_seconds
    launches = [p for _, kind, p in run.trace_events
                if kind == "launch" and p["kind"] == "fm"]
    if any("trips" not in p for p in launches):
        return None
    trips = sum(p["trips"] for p in launches)
    device_s = module_seconds(run.trace, "fm_refine_multi")
    if not trips or device_s <= 0:
        return None
    return 1e6 * device_s / trips
