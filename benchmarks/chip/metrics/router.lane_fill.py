"""Lanes that carried work over lanes dispatched, summed over the
router's ``launch`` events in the window (``service/router.py``)."""


def read(run):
    launches = [p for _, kind, p in run.events if kind == "launch"]
    pad = sum(p["lanes_pad"] for p in launches)
    if not pad:
        return None
    return 100.0 * sum(p["lanes"] for p in launches) / pad
