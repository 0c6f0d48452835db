"""Router-fed nested dissection over many graphs at once (DESIGN.md §3).

``core.nd`` recurses depth-first through one ND tree, dispatching each
subproblem's kernels on its own.  The scheduler instead expresses every
request's whole ND recursion as ONE work-yielding task tree
(``_nd_node_task`` — leaves, component splits and the separator-ordering
host steps inline, subtrees spawned as sibling tasks) and submits all
requests to a shared ``service.router.WaveRouter``.  Every router wave
gathers the outstanding matching / BFS / FM work of every live subtree
of every request and executes it bucketed — one vmap dispatch per ELL
bucket per wave, with lanes from different *requests* stacking into the
same launch.  The left/right subgraphs of every dissection are
independent (paper §3.1) — exactly the parallelism the paper spreads
over processes, here spread over the lanes of a batched kernel dispatch.
``distributed_order_batch`` funnels the deferred sequential subtrees of
ALL its requests through one ``order_batch`` call too, so the endgames
of every ND branch of every ordering share these waves.

Work items run the same computation whether batched or not, the helpers
(``leaf_perm`` / ``resolve_separator`` / ``split_by_separator`` /
``separator_perm``) are pure per-subgraph, and ``Ordering.assemble``
sorts fragments by start — so ``order_batch`` returns permutations
identical to looped ``nested_dissection`` calls regardless of wave
composition.
"""
from __future__ import annotations

from typing import List, Sequence, Union

import numpy as np

from repro import obs
from repro.core import dgraph as _dg
from repro.core.dnd import _Spawn
from repro.core.graph import Graph
from repro.core.nd import (NDConfig, child_nprocs, child_seeds,
                           component_seed, effective_nproc, leaf_perm,
                           resolve_separator, separator_perm,
                           separator_task, split_by_separator)
from repro.core.ordering import Ordering


def _as_list(x, n: int) -> list:
    if isinstance(x, (list, tuple)):
        assert len(x) == n
        return list(x)
    return [x] * n


def _nd_node_task(g: Graph, gids: np.ndarray, seed: int, nproc: int,
                  cfg: NDConfig, ordering: Ordering, node, start: int,
                  hints=None, rec=None, path: str = ""):
    """One ND tree node as a router task: order ``g`` into ``ordering``.

    Leaves and connected-component splits are handled inline on the
    host plane; separators run through ``nd.separator_task`` (yielding
    its device works to the router); the two separated halves spawn as
    sibling subtasks, so all of a request's — and all concurrent
    requests' — same-depth subproblems join the same waves.

    ``hints`` / ``rec`` thread the warm-start surface (DESIGN.md §7)
    through the recursion: ``path`` names this node in the ND tree
    (root ``""``, dissection children ``.0``/``.1``, components
    ``.c<k>``); a hint at this path short-circuits the separator
    pipeline through ``separator_task(warm_part=...)`` (re-validated on
    ``g``, so stale hints fall back cold per node), and ``rec`` records
    every *resolved* split so a completed tree can seed later
    structurally identical requests.  Replaying the cached splits
    reproduces the cached recursion shape on any same-topology graph —
    induced subgraphs of equal structure under equal parts are equal
    structures — so paths align between record and replay by
    construction.

    The host steps run under ``stage:*`` spans, each between two yields:
    ``leaf_order`` (minimum degree on a leaf), ``split`` (the component
    split, ``resolve_separator`` and ``split_by_separator``) and
    ``sep_order`` (the separator's own order).
    """
    if g.n <= cfg.leaf_size:
        with _dg.stage("leaf_order"):
            ordering.add_leaf(node, start, gids[leaf_perm(g, seed)])
        return
    with _dg.stage("split"):
        comp = g.components()
        ncomp = int(comp.max()) + 1
        subs = []
        if ncomp > 1:                   # independent parts: no separator
            off = start
            for c in range(ncomp):
                sub, old = g.induced_subgraph(comp == c)
                child = ordering.add_internal(node, off, sub.n)
                subs.append(_nd_node_task(sub, gids[old],
                                          component_seed(seed, c), nproc,
                                          cfg, ordering, child, off,
                                          hints, rec, f"{path}.c{c}"))
                off += sub.n
    if subs:
        yield _Spawn(subs)
        return
    part = yield from separator_task(
        g, seed, effective_nproc(g.n, nproc, cfg), cfg,
        warm_part=None if hints is None else hints.get(path))
    with _dg.stage("split"):
        part = resolve_separator(g, seed, part, cfg)
        if part is not None:
            (g0, old0), (g1, old1), (gs, olds) = split_by_separator(g, part)
    if part is None:                    # could not split
        with _dg.stage("leaf_order"):
            ordering.add_leaf(node, start, gids[leaf_perm(g, seed)])
        return
    if rec is not None:
        rec[path] = part
    p0, p1 = child_nprocs(nproc)
    s0, s1 = child_seeds(seed)
    c0 = ordering.add_internal(node, start, g0.n)
    c1 = ordering.add_internal(node, start + g0.n, g1.n)
    with _dg.stage("sep_order"):
        sperm = separator_perm(gs, seed)
        ordering.add_leaf(node, start + g0.n + g1.n, gids[olds[sperm]],
                          "sep")
    yield _Spawn([
        _nd_node_task(g0, gids[old0], s0, p0, cfg, ordering, c0, start,
                      hints, rec, path + ".0"),
        _nd_node_task(g1, gids[old1], s1, p1, cfg, ordering, c1,
                      start + g0.n, hints, rec, path + ".1"),
    ])


def request_task(g: Graph, seed: int, nproc: int, cfg: NDConfig,
                 ordering: Ordering, hints=None, rec=None,
                 path: str = ""):
    """Root ND task of one host-graph request (the service pump's unit).

    The service admits one of these per request onto its persistent
    ``WaveRouter`` and assembles ``ordering`` once the root completes —
    same task tree ``order_batch`` builds, exposed so admission can be
    incremental (and warm-started / recorded via ``hints`` / ``rec``).
    """
    return _nd_node_task(g, np.arange(g.n, dtype=np.int64), seed, nproc,
                         cfg, ordering, ordering.root, 0,
                         hints=hints, rec=rec, path=path)


def order_batch(graphs: Sequence[Graph],
                seeds: Union[int, Sequence[int]] = 0,
                nprocs: Union[int, Sequence[int]] = 1,
                cfgs: Union[NDConfig, Sequence[NDConfig], None] = None,
                tags: Union[Sequence, None] = None
                ) -> List[np.ndarray]:
    """Order many graphs through one shared wave router.

    Returns one permutation per graph, identical to
    ``[nested_dissection(g, seed, nproc, cfg) for ...]``.  ``tags``
    (optional, one per graph) attribute each request's lanes in the
    router's wave summaries — ``distributed_order_batch`` uses it to
    keep its merged endgame attributed to the originating distributed
    requests.
    """
    from repro.service.router import WaveRouter
    from repro.util import enable_compile_cache
    enable_compile_cache()
    n_req = len(graphs)
    seeds = _as_list(seeds, n_req)
    nprocs = _as_list(nprocs, n_req)
    cfgs = _as_list(cfgs or NDConfig(), n_req)
    if tags is not None:
        assert len(tags) == n_req
    orderings = [Ordering(g.n) for g in graphs]

    router = WaveRouter()
    with obs.span("sched:batch", requests=n_req):
        for i, g in enumerate(graphs):
            root = request_task(g, seeds[i], nprocs[i], cfgs[i],
                                orderings[i])
            router.submit(root, tag=i if tags is None else tags[i])
        router.run()

    perms = []
    for g, ordering in zip(graphs, orderings):
        perm = ordering.assemble()
        assert np.array_equal(np.sort(perm), np.arange(g.n)), \
            "not a permutation"
        perms.append(perm)
    return perms
