"""Whether the answers the window produced are correct.

Every request submitted in the window must come back with status ``ok``
and a permutation of its vertices (exact: limit 0).  A sample of them,
drawn from the seed and holding the largest graph, is ordered again by
the plain reference (``reference.nested_dissection``) and read for the
worst ratio of the program's fill to the reference's (``opc_ratio``) and
the worst imbalance of the program's top split, from the elimination
tree (``top_imbalance``) and from the order itself (``split_imbalance``).
Those the configuration's ``check`` gives a limit must stay within it;
the others are logged as not compared.  Nothing here imports the
program.
"""
from __future__ import annotations

import numpy as np

import reference


def sample(records, seed: int, k: int):
    """``k`` of the answered records, drawn from ``seed``, always with
    the largest graph among them."""
    ok = [r for r in records if r.status == "ok" and r.perm is not None]
    if len(ok) <= k:
        return ok
    rng = np.random.default_rng([abs(int(seed)), 7])
    largest = max(range(len(ok)), key=lambda i: (ok[i].n, -i))
    rest = [i for i in range(len(ok)) if i != largest]
    pick = [largest] + list(rng.choice(rest, k - 1, replace=False))
    return [ok[i] for i in sorted(pick)]


#: the numbers a sample is read for; a configuration compares those its
#: ``check`` gives a limit
READINGS = ("opc_ratio", "top_imbalance", "split_imbalance")


def readings(n: int, edges, perm, control: str = "") -> dict:
    """``opc_ratio`` (the fill of ``perm`` over the reference ordering's
    fill), ``top_imbalance`` and ``split_imbalance`` of ``perm``.

    ``control`` puts a control in the program's place: ``"bfloat16"``,
    the reference with its balance sums and gains one precision below
    the configuration's float32; ``"separators_first"``, the reference
    with each separator eliminated before the parts it separates.
    """
    xadj, adjncy = reference.csr(n, edges)
    ref = reference.nested_dissection(xadj, adjncy)
    if control == "bfloat16":
        import ml_dtypes
        perm = reference.nested_dissection(xadj, adjncy,
                                           dtype=ml_dtypes.bfloat16)
    elif control == "separators_first":
        perm = reference.nested_dissection(xadj, adjncy,
                                           separator_last=False)
    elif control:
        raise ValueError(f"no control {control!r}")
    return {"opc_ratio": reference.opc(xadj, adjncy, perm)
            / reference.opc(xadj, adjncy, ref),
            "top_imbalance": reference.top_imbalance(xadj, adjncy, perm),
            "split_imbalance": reference.split_imbalance(xadj, adjncy, perm)}


def check(records, pool, seed: int, limits: dict, log=lambda msg: None):
    """``(correct, numbers)`` where ``numbers`` maps each compared name
    to ``[reading, limit]``.  ``log`` gets each sampled graph's readings
    and the worst of each reading that is not compared."""
    counted = [r for r in records if r.counted]
    unresolved = sum(1 for r in counted if r.t_resolve is None)
    failed = sum(1 for r in counted
                 if r.t_resolve is not None and r.status != "ok")
    invalid = sum(1 for r in counted if r.status == "ok"
                  and not reference.is_permutation(r.perm, r.n))
    worst = dict.fromkeys(READINGS, 0.0)
    picked = sample([r for r in counted if r.status == "ok"
                     and reference.is_permutation(r.perm, r.n)],
                    seed, int(limits["sample"]))
    for r in picked:
        req = pool[r.index]
        got = readings(req.n, req.edges, r.perm)
        log(f"check sample {r.index} (n {req.n}): "
            + ", ".join(f"{name} {v}" for name, v in got.items()))
        for name, v in got.items():
            worst[name] = max(worst[name], v)
    for name, v in worst.items():
        if name not in limits:
            log(f"check {name}: {v} (not compared)")
    worst = {name: v for name, v in worst.items() if name in limits}
    numbers = {"unresolved": [unresolved, 0], "failed": [failed, 0],
               "invalid_perm": [invalid, 0]}
    numbers.update((name, [v, limits[name]]) for name, v in worst.items())
    correct = (unresolved == 0 and failed == 0 and invalid == 0
               and bool(picked)
               and all(v <= limits[name] for name, v in worst.items()))
    return correct, numbers
