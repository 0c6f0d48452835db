"""Share of the window spent in the host steps of nested dissection
between waves: the ``stage`` events ``band`` (``core/nd.py``: project,
band extract, ELL build, project back), ``split``, ``leaf_order`` and
``sep_order`` (``service/scheduler.py``), summed over the window."""

STAGES = ("band", "split", "leaf_order", "sep_order")


def read(run):
    s = sum(p["seconds"] for _, kind, p in run.events
            if kind == "stage" and p["name"] in STAGES)
    return 100.0 * s / run.seconds if s > 0 else None
