"""Dispatches inside the window billed as a first use (``stage`` events
with ``compile`` true): executables traced, and built or loaded from
the persistent cache, while the window runs (``obs.first_use``)."""


def read(run):
    return float(sum(1 for _, kind, p in run.events
                     if kind == "stage" and p["compile"]))
