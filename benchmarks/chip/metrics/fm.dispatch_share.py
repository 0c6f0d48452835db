"""Host time blocked in steady FM dispatches (``stage`` events of
``fm`` that are not a first use) over the window (``core/fm.py``)."""


def read(run):
    s = sum(p["seconds"] for _, kind, p in run.events
            if kind == "stage" and p["name"] == "fm" and not p["compile"])
    return 100.0 * s / run.seconds if s > 0 else None
