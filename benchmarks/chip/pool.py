"""Request graphs: the generators the cells use and the pool a run draws
its requests from, in an order made from ``--seed``.

The generators are copies of ``grid3d`` and ``circuit`` from
``repro.graphs.generators`` that return edge lists, so the benchmark's
inputs cannot move when the program's generators change.  A
configuration names its generator with the key ``generator``.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Dict, List, Tuple

import numpy as np

Edges = Tuple[int, np.ndarray]          # (n, (m, 2) int64 edge list)


def grid3d(nx: int, ny: int, nz: int, stencil: int = 7) -> Edges:
    """7-point or 27-point stencil on an nx x ny x nz grid (a hexahedral
    finite-element mesh with trilinear elements for 27)."""
    idx = np.arange(nx * ny * nz).reshape(nx, ny, nz)
    e = [np.stack([idx[:-1].ravel(), idx[1:].ravel()], 1),
         np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], 1),
         np.stack([idx[:, :, :-1].ravel(), idx[:, :, 1:].ravel()], 1)]
    if stencil == 27:
        for dx in (-1, 0, 1):
            for dy in (-1, 0, 1):
                for dz in (-1, 0, 1):
                    if (dx, dy, dz) <= (0, 0, 0):
                        continue
                    sa = idx[max(0, -dx):nx - max(0, dx),
                             max(0, -dy):ny - max(0, dy),
                             max(0, -dz):nz - max(0, dz)]
                    sb = idx[max(0, dx):nx - max(0, -dx),
                             max(0, dy):ny - max(0, -dy),
                             max(0, dz):nz - max(0, -dz)]
                    e.append(np.stack([sa.ravel(), sb.ravel()], 1))
    return nx * ny * nz, np.concatenate(e).astype(np.int64)


def circuit(n: int, seed: int = 0, fanout: float = 2.4) -> Edges:
    """Circuit-simulation analog: a chain plus random low-degree fanout,
    90% of it local (spans under 50) and 10% long nets."""
    rng = np.random.default_rng(seed)
    chain = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    k = int(n * fanout)
    src = rng.integers(0, n, k)
    span = np.where(rng.random(k) < 0.9,
                    rng.integers(1, 50, k), rng.integers(1, n, k))
    dst = (src + span) % n
    return n, np.concatenate([chain, np.stack([src, dst], 1)]).astype(
        np.int64)


def _relabel(edges: Edges, rng) -> Edges:
    n, e = edges
    return n, rng.permutation(n)[e]


def _fe_mesh(cfg: dict, rng, base: dict) -> Edges:
    if "mesh" not in base:
        base["mesh"] = grid3d(int(cfg["nx"]), int(cfg["ny"]),
                              int(cfg["nz"]), stencil=int(cfg["stencil"]))
    return _relabel(base["mesh"], rng)


def _circuit(cfg: dict, rng, base: dict) -> Edges:
    n = int(rng.integers(int(cfg["n_min"]), int(cfg["n_max"]) + 1))
    return circuit(n, seed=int(rng.integers(0, 2**31)),
                   fanout=float(cfg["fanout"]))


#: configuration ``generator`` -> one request's graph from the rng
GENERATORS: Dict[str, Callable] = {"grid3d": _fe_mesh, "circuit": _circuit}


@dataclasses.dataclass
class Request:
    index: int
    n: int
    edges: np.ndarray                   # (m, 2) int64
    seed: int                           # the ordering seed sent with it
    fingerprint: str


def fingerprint(n: int, edges: np.ndarray) -> str:
    """Content hash of the undirected graph (order of edges ignored)."""
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    key = np.unique(lo[lo != hi] * n + hi[lo != hi])
    return hashlib.blake2b(np.int64(n).tobytes() + key.tobytes(),
                           digest_size=16).hexdigest()


def build_set(cfg: dict, seed: int, count: int) -> List[Request]:
    """``count`` distinct request graphs of configuration ``cfg``, the
    same for the same ``seed``; a graph whose fingerprint is already in
    the set is drawn again."""
    gen = GENERATORS[cfg["generator"]]
    rng = np.random.default_rng(abs(int(seed)))
    base: dict = {}
    seen = set()
    pool: List[Request] = []
    while len(pool) < count:
        n, e = gen(cfg, rng, base)
        fp = fingerprint(n, e)
        order_seed = int(rng.integers(0, 2**31))
        if fp in seen:
            continue
        seen.add(fp)
        pool.append(Request(len(pool), n, e, order_seed, fp))
    return pool


def build_pool(cfg: dict, seed: int) -> List[Request]:
    """The run's requests: the configuration's fixed set of ``set_size``
    graphs (made from its ``set_seed``), in an order drawn from the
    run's ``seed`` within blocks of ``set_block``.

    Every seed draws from the same graphs, so the checkout's first run
    and later ones meet the same shapes; the set is many times what a
    window orders, so a faster program does not run out of it.  The
    order moves graphs only within their block, so a window that orders
    whole blocks does the same work under every seed.
    """
    count, block = int(cfg["set_size"]), int(cfg["set_block"])
    fixed = build_set(cfg, int(cfg["set_seed"]), count)
    rng = np.random.default_rng([abs(int(seed)), 1])
    order = np.concatenate([b + rng.permutation(min(block, count - b))
                            for b in range(0, count, block)])
    return [dataclasses.replace(fixed[i], index=k)
            for k, i in enumerate(order)]
