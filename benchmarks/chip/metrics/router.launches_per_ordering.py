"""Device launches (``launch`` events) in the window per ordering
completed in it (``service/router.py``)."""


def read(run):
    if not run.completed:
        return None
    return sum(1 for _, kind, _ in run.events if kind == "launch") / len(
        run.completed)
