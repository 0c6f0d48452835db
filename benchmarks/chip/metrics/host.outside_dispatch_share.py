"""Share of the window in which no device dispatch was open: one minus
the union of the ``dispatch:*`` spans over the window.  That is the time
of the host control plane (``core/nd.py`` recursion and endgame,
``service/scheduler.py``, the router's bookkeeping)."""


def read(run):
    if run.spans is None:
        return None
    iv = sorted((max(s.t0, run.t_open), min(s.t1, run.t_close))
                for s in run.spans if s.name.startswith("dispatch:")
                and s.t1 is not None)
    covered, end = 0.0, run.t_open
    for a, b in iv:
        a = max(a, end)
        if b > a:
            covered += b - a
            end = b
    return 100.0 * (1.0 - covered / run.seconds)
