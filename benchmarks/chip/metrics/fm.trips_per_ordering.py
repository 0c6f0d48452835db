"""Serial iterations of the FM move loop per ordering: the ``trips`` of
the window's ``fm`` launches (``core/fm.py``: over passes, the most moves
any lane ran, counted in ``fm_move_loop``'s carry on the device), over
the orderings completed in the window."""


def read(run):
    launches = [p for _, kind, p in run.events
                if kind == "launch" and p["kind"] == "fm"]
    if (not run.completed or not launches
            or any("trips" not in p for p in launches)):
        return None
    return sum(p["trips"] for p in launches) / len(run.completed)
