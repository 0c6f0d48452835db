"""Distributed graph structure + halo exchange (paper §2.1), shard_map form.

The paper's structure maps onto JAX as stacked per-shard arrays with a
``parts`` mesh axis:

  * ``vtxdist``      — the paper's ``procvrttab``: global vertex ranges per
    shard (duplicated everywhere, owner lookup by range search);
  * ``nbr_gst``      — the paper's ``edgegsttab``: ELL adjacency in *compact
    local indexing* where indices < n_loc_max are local and indices ≥
    n_loc_max address the ghost slots, numbered by (owner, global id) — the
    cache-friendly agglomeration order of §2.1;
  * ``ewgt_gst``     — matching ELL edge weights (heavy-edge matching on
    coarse levels needs them);
  * ``ghost_gid``    — global ids of ghost slots per shard (the receive
    manifest of the halo exchange).

``halo_exchange`` diffuses local vertex values to the ghost copies on
neighboring shards: the reference implementation is an ``all_gather`` over
the parts axis + gather (dense collective — the TPU-idiomatic replacement
for MPI point-to-point; DESIGN.md §2 discusses the trade).

All device functions take the per-graph arrays (``vtxdist``, ``ghost_gid``,
…) as *traced arguments* and are cached per padded shape, so the jit cache
is shared across every subgraph of a nested-dissection recursion that lands
in the same power-of-two bucket (same bucketing the centralized data plane
uses, ``repro.util.pow2``).

Scalability note (matching the paper): no shard stores ghost *adjacency* —
only ghost values — so per-shard memory is O(local arcs).

Two kinds of routines live here (DESIGN.md §4):

  * **device collectives** (``halo_exchange_fn``, ``distributed_bfs``,
    ``distributed_matching``) — ``shard_map`` programs over the parts axis.
    Each is the one-lane special case of its **lane-stacked** form
    (``halo_exchange_stacked``, ``distributed_bfs_stacked``,
    ``distributed_matching_stacked``): same-bucket graphs stack along a
    leading lane axis and ONE launch — with one fused ``all_gather`` per
    internal round for the whole stack — serves all of them.  Per-lane
    reductions are within-lane, so lane-stacked results are bit-identical
    to singleton execution (the frontier driver of ``core.dnd`` relies on
    this, exactly as ``fm.execute_fm_works`` does for FM lanes).
  * **structure rebuilds** (``distribute``, ``dgraph_induced``,
    ``dgraph_fold``, ``dgraph_coarsen``) — host-side reshuffles of the
    stacked arrays that model the owner-routed ``MPI_Alltoallv`` of the
    paper's redistribution steps.  They stage the routed arcs in flat
    arrays (the analog of the exchange's send/receive buffers, O(arcs)
    words), never a centralized CSR graph.

All instrumentation hangs off ONE entry point, ``instrument()``: the
centralizing gathers (``to_host`` / ``unshard_vector`` element counts, the
gather-free guarantee), host-level halo exchanges (the per-round band sync
budget), per-launch collective counters (kind, lanes, all_gather words —
how the frontier driver's launch budget is asserted), sharded-band
refinement stats, per-stage wall-clock, and frontier wave summaries.
``track_gathers`` / ``track_halos`` (and ``dnd.track_band_stats``) are
thin compatibility views over the same block.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core.graph import Graph
from repro.core.matching import hash_mix, hash_unit
from repro.util import pow2


@dataclasses.dataclass
class DGraph:
    """Host-resident description of a P-way distributed graph."""
    vtxdist: np.ndarray        # (P+1,) global ranges
    nbr_gst: np.ndarray        # (P, n_loc_max, dmax) compact local/ghost ids
    ewgt_gst: np.ndarray       # (P, n_loc_max, dmax) edge weights (0 pad)
    ghost_gid: np.ndarray      # (P, n_ghost_max) global ids of ghosts (-1 pad)
    n_loc: np.ndarray          # (P,) real local counts
    n_ghost: np.ndarray        # (P,) real ghost counts
    vwgt: np.ndarray           # (P, n_loc_max)

    @property
    def nparts(self) -> int:
        return len(self.vtxdist) - 1

    @property
    def n_loc_max(self) -> int:
        return self.nbr_gst.shape[1]

    @property
    def n_global(self) -> int:
        return int(self.vtxdist[-1])


def _build_dgraph(vtxdist: np.ndarray, src: np.ndarray, dst: np.ndarray,
                  w: np.ndarray, vwgt: np.ndarray,
                  bucket: bool = True) -> DGraph:
    """Assemble the stacked shard arrays from an owner-routed arc list.

    The shared back end of every structure rebuild (``distribute``,
    ``dgraph_induced``, ``dgraph_fold``, ``dgraph_coarsen``).  ``src`` /
    ``dst`` / ``w`` are flat *directed* arc arrays in global ids (each
    undirected edge appears in both directions) — the staging buffers of
    the owner-routed Alltoallv that the paper's redistribution performs;
    ``vwgt`` is the flat (n,) vertex-weight vector in global-id order.
    Parallel arcs are deduplicated with accumulated weights (exactly
    ``Graph.from_edges``'s canonicalization), so rebuilding through here
    matches the centralized builders arc-for-arc.

    Timed as the ``rebuild`` stage (every structure rebuild funnels
    through here), so the bench's per-stage wall-clock breakdown can
    separate host reshuffles from device collectives.
    """
    with stage("rebuild"):
        return _build_dgraph_impl(vtxdist, src, dst, w, vwgt, bucket=bucket)


def _build_dgraph_impl(vtxdist, src, dst, w, vwgt, bucket=True) -> DGraph:
    vtxdist = np.asarray(vtxdist, dtype=np.int64)
    nparts = len(vtxdist) - 1
    n = int(vtxdist[-1])
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    w = np.asarray(w, dtype=np.int64)
    if len(src):
        order = np.lexsort((dst, src))
        src, dst, w = src[order], dst[order], w[order]
        uniq = np.concatenate(
            [[True], (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])])
        seg = np.cumsum(uniq) - 1
        wacc = np.zeros(seg[-1] + 1, dtype=np.int64)
        np.add.at(wacc, seg, w)
        src, dst, w = src[uniq], dst[uniq], wacc

    n_loc = np.diff(vtxdist)
    n_loc_max = int(n_loc.max()) if nparts else 1
    deg = np.bincount(src, minlength=max(n, 1))[:max(n, 1)]
    dmax = int(deg.max()) if len(src) else 1
    if bucket:
        n_loc_max = pow2(max(n_loc_max, 1), 8)
        dmax = pow2(max(dmax, 1), 4)
    n_loc_max = max(n_loc_max, 1)
    dmax = max(dmax, 1)

    owner = np.searchsorted(vtxdist, np.arange(n), side="right") - 1
    p_src = owner[src]
    xadj = np.concatenate([[0], np.cumsum(deg)])
    col = np.arange(len(dst)) - xadj[src]
    li_src = src - vtxdist[p_src]
    remote = p_src != owner[dst]

    # ghost manifests: unique (shard, gid) pairs among remote arc heads.
    # Ascending gid is ascending (owner, gid) because vtxdist is sorted —
    # the §2.1 cache-friendly agglomeration order.
    keys = p_src[remote] * np.int64(max(n, 1)) + dst[remote]
    uk = np.unique(keys)
    gp = uk // max(n, 1)
    ggid = uk % max(n, 1)
    counts = np.bincount(gp, minlength=nparts)
    offs = np.concatenate([[0], np.cumsum(counts)])
    gslot = np.arange(len(uk)) - offs[gp]
    n_ghost = counts.astype(np.int64)
    n_ghost_max = max(int(n_ghost.max()) if nparts else 0, 1)
    if bucket:
        n_ghost_max = pow2(n_ghost_max, 4)
    ghost_gid = -np.ones((nparts, n_ghost_max), dtype=np.int64)
    ghost_gid[gp, gslot] = ggid

    nbr_gst = -np.ones((nparts, n_loc_max, dmax), dtype=np.int32)
    ewgt_gst = np.zeros((nparts, n_loc_max, dmax), dtype=np.int32)
    cidx = dst - vtxdist[owner[dst]] if len(dst) else dst
    if len(uk):
        cidx[remote] = n_loc_max + gslot[np.searchsorted(uk, keys)]
    nbr_gst[p_src, li_src, col] = cidx
    ewgt_gst[p_src, li_src, col] = w

    vwgt_sh = np.zeros((nparts, n_loc_max), dtype=np.int64)
    vwgt_sh[owner, np.arange(n) - vtxdist[owner]] = np.asarray(vwgt, np.int64)
    return DGraph(vtxdist, nbr_gst, ewgt_gst, ghost_gid, n_loc, n_ghost,
                  vwgt_sh)


def distribute(g: Graph, nparts: int,
               vtxdist: Optional[np.ndarray] = None,
               bucket: bool = True) -> DGraph:
    """Distribute a host graph (the paper's user-defined ranges).

    Args:
      g: centralized host graph (symmetric CSR).
      nparts: number of shards P.
      vtxdist: optional (P+1,) custom ownership ranges (the coarse graphs
        of distributed coarsening keep coarse vertices on the owner of
        their representative); the default is a balanced block
        distribution.
      bucket: round padded shard shapes up to powers of two so jitted
        collectives are reused across same-bucket subgraphs.

    Returns a ``DGraph`` whose stacked arrays hold g partitioned by
    ``vtxdist`` ranges.
    """
    n = g.n
    if vtxdist is None:
        vtxdist = np.linspace(0, n, nparts + 1).astype(np.int64)
    else:
        vtxdist = np.asarray(vtxdist, dtype=np.int64)
        assert len(vtxdist) == nparts + 1 and vtxdist[-1] == n
    src = np.repeat(np.arange(n, dtype=np.int64), g.degrees())
    return _build_dgraph(vtxdist, src, g.adjncy, g.adjwgt, g.vwgt,
                         bucket=bucket)


@functools.lru_cache(maxsize=None)
def make_parts_mesh(nparts: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < nparts:
        raise RuntimeError(
            f"a {nparts}-part mesh needs {nparts} devices; platform "
            f"{devs[0].platform!r} has {len(devs)}")
    return Mesh(np.array(devs[:nparts]), ("parts",))


# ------------------------------------------------------------------ #
# bounded jit-builder cache (router-managed data-plane policy)
# ------------------------------------------------------------------ #
class _JitCache:
    """LRU over the stacked-collective jit executables.

    A long-lived service accumulates (bucket, lanes, …) shape keys
    without bound — every new pow2 bucket × lane count × rounds/width
    combination is a fresh executable.  This cache caps them: keys are
    *identical* to the ``obs.first_use`` dispatch keys, so an eviction
    calls ``obs.forget_use(key)`` and the re-build after re-insertion
    bills itself as a compile again (not a suspiciously slow dispatch).
    The live entry count is mirrored into the ``repro_jit_cache_size``
    metric (evictions counted by ``repro_jit_cache_evictions_total``).

    Capacity comes from ``RouterConfig.jit_cache_capacity`` via
    ``set_jit_cache_capacity`` (env default ``REPRO_JIT_CACHE_CAP``);
    ``repro.core`` never imports the service layer, so the setter is
    the interface.
    """

    def __init__(self, capacity: int):
        self._cap = max(int(capacity), 1)
        self._entries: "OrderedDict[Tuple, object]" = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key: Tuple, builder):
        with self._lock:
            fn = self._entries.get(key)
            if fn is not None:
                self._entries.move_to_end(key)
                return fn
        fn = builder()                  # build outside the lock (slow)
        with self._lock:
            if key in self._entries:    # lost a build race: keep theirs
                fn = self._entries[key]
            else:
                self._entries[key] = fn
                obs.REGISTRY.inc("repro_jit_cache_size")
            self._entries.move_to_end(key)
            self._trim()
        return fn

    def _trim(self) -> None:            # caller holds the lock
        while len(self._entries) > self._cap:
            old_key, _ = self._entries.popitem(last=False)
            obs.forget_use(old_key)
            obs.REGISTRY.inc("repro_jit_cache_size", -1.0)
            obs.REGISTRY.inc("repro_jit_cache_evictions_total")

    def set_capacity(self, capacity: int) -> None:
        with self._lock:
            self._cap = max(int(capacity), 1)
            self._trim()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


_JIT_CACHE = _JitCache(int(os.environ.get("REPRO_JIT_CACHE_CAP", "64")))


def set_jit_cache_capacity(capacity: int) -> None:
    """Bound the stacked-collective jit cache (RouterConfig surface)."""
    _JIT_CACHE.set_capacity(capacity)


def jit_cache_size() -> int:
    """Live stacked-collective executables (tests / metrics cross-check)."""
    return len(_JIT_CACHE)


#: compact the matching proposal gather (RouterConfig surface; lossless,
#: see ``distributed_matching_stacked``)
_MATCH_COMPACT = os.environ.get("REPRO_MATCH_COMPACT", "1") != "0"


def set_match_compact(on: bool) -> None:
    global _MATCH_COMPACT
    _MATCH_COMPACT = bool(on)


# ------------------------------------------------------------------ #
# instrumentation: one entry point for every counter (DESIGN.md §4)
# ------------------------------------------------------------------ #
@dataclasses.dataclass(eq=False)      # identity semantics: nested blocks
class Instrumentation:                # with equal contents must not alias
    """Counters recorded by one ``instrument()`` block.

    ``gathers``   — one ``(kind, n_elements)`` per centralizing gather
      (``to_host`` / ``unshard_vector``); the gather-free tests bound it.
    ``halos``     — exchanged element count (P · n_loc_max words) per
      host-level halo exchange, one entry per *work*: a lane-stacked
      launch serving L works appends L entries, so this keeps measuring
      the per-task synchronization budget the band tests bound.
      Exchanges fused inside jitted sweeps (BFS relaxations, matching
      rounds) are not counted.
    ``launches``  — one dict per device launch:
      ``{"kind", "nparts", "lanes", "lanes_pad", "bucket", "rounds",
      "words"}``.  Distributed ``shard_map`` collectives record kinds
      ``dhalo`` / ``dbfs`` / ``dmatch`` with ``words`` = the launch's
      total ``all_gather`` traffic in elements summed over its internal
      rounds; the centralized bucketed executors record ``fm`` / ``bfs``
      / ``match`` (nparts 0, words 0) per dispatch.  This is the counter
      behind the frontier driver's launch-budget assertions (the wave
      summaries count *these records*, not their own bookkeeping) and
      the matching grant-compaction measurement.
    ``band_stats``— one dict per sharded-band refinement (appended by
      ``dnd``'s band task; see ``dnd.track_band_stats``).
    ``stage_s``   — accumulated wall-clock seconds per pipeline stage
      (``match`` / ``bfs`` / ``halo`` / ``fm`` / ``rebuild`` /
      ``endgame``).  Stages are attribution *categories*, not disjoint
      intervals: ``endgame`` times the whole deferred-subtree batch and
      therefore contains the ``fm`` / ``bfs`` / ``match`` shares its
      executors bill.
    ``stage_detail`` — per stage, the compile/dispatch split:
      ``{stage: {"compile_s", "dispatch_s"}}``.  A dispatch whose jit
      cache key (mirroring the builder's ``lru_cache`` key) is seen for
      the first time bills its whole wall to ``compile_s`` (trace +
      lower + XLA compile, or a persistent-cache load); steady-state
      repeats bill ``dispatch_s``.
    ``waves``     — one summary dict per frontier wave (appended by the
      frontier driver): outstanding works / shape buckets / launches /
      wall-clock (``t_s``) / per-stage seconds (``stage_s``) by kind.
    """
    gathers: List[Tuple[str, int]] = dataclasses.field(default_factory=list)
    halos: List[int] = dataclasses.field(default_factory=list)
    launches: List[dict] = dataclasses.field(default_factory=list)
    band_stats: List[dict] = dataclasses.field(default_factory=list)
    stage_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    waves: List[dict] = dataclasses.field(default_factory=list)
    stage_detail: Dict[str, Dict[str, float]] = \
        dataclasses.field(default_factory=dict)

    def on_event(self, kind: str, payload: dict) -> None:
        """Event-bus entry point (called with the bus lock held, so the
        read-modify-write ``stage_s`` accumulation is atomic under
        concurrent emitters)."""
        if kind == "gather":
            self.gathers.append((payload["kind"], payload["n"]))
        elif kind == "halo":
            self.halos.append(payload["n"])
        elif kind == "launch":
            self.launches.append(payload)      # the shared launch record
        elif kind == "band_stats":
            self.band_stats.append(payload)
        elif kind == "stage":
            name, sec = payload["name"], float(payload["seconds"])
            self.stage_s[name] = self.stage_s.get(name, 0.0) + sec
            d = self.stage_detail.setdefault(
                name, {"compile_s": 0.0, "dispatch_s": 0.0})
            d["compile_s" if payload.get("compile") else "dispatch_s"] += sec
        elif kind == "wave":
            self.waves.append(payload)         # the shared wave summary


@contextlib.contextmanager
def instrument():
    """Record all data-plane counters executed inside the block.

    Yields an ``Instrumentation``.  Blocks nest: every active block
    receives every event (so a ``track_halos()`` view inside a broader
    ``instrument()`` sees the same exchanges the outer block does).
    Registration lives on the ``repro.obs`` event bus, whose lock makes
    concurrent emitters (a service drain thread under a caller-thread
    reader) safe; removal is **by identity** so nested blocks with equal
    contents never evict each other.
    """
    ins = Instrumentation()
    obs.register_collector(ins)
    try:
        yield ins
    finally:
        obs.unregister_collector(ins)


@contextlib.contextmanager
def track_gathers():
    """Compat view over ``instrument()``: yields its ``gathers`` list."""
    with instrument() as ins:
        yield ins.gathers


@contextlib.contextmanager
def track_halos():
    """Compat view over ``instrument()``: yields its ``halos`` list."""
    with instrument() as ins:
        yield ins.halos


def _note_gather(kind: str, size: int) -> None:
    obs.emit("gather", {"kind": kind, "n": int(size)})


def _note_halo(size: int) -> None:
    obs.emit("halo", {"n": int(size)})


def _note_launch(kind: str, nparts: int, lanes: int, lanes_pad: int,
                 bucket: Tuple[int, ...], rounds: int, words: int,
                 **extra) -> None:
    """``extra`` carries launch-specific metadata: ``tags`` (per-lane
    request attribution from the wave router), ``cap`` / ``words_dense``
    (the matching proposal-gather compaction measurement)."""
    payload = {"kind": kind, "nparts": int(nparts),
               "lanes": int(lanes), "lanes_pad": int(lanes_pad),
               "bucket": tuple(bucket), "rounds": int(rounds),
               "words": int(words)}
    payload.update(extra)
    obs.emit("launch", payload)


def _note_band_stats(stats: dict) -> None:
    obs.emit("band_stats", stats)


def _note_stage(name: str, seconds: float, compile: bool = False) -> None:
    obs.emit("stage", {"name": name, "seconds": float(seconds),
                       "compile": compile})


def _note_wave(summary: dict) -> None:
    obs.emit("wave", summary)


@contextlib.contextmanager
def stage(name: str):
    """Time a pipeline stage into every active ``instrument()`` block,
    and open a ``stage:{name}`` span when tracing is enabled (host-side
    stages — ``rebuild``, ``endgame`` — get their trace attribution
    here; device dispatches use ``obs.timed_dispatch`` instead)."""
    t0 = time.perf_counter()
    with obs.span(f"stage:{name}"):
        try:
            yield
        finally:
            _note_stage(name, time.perf_counter() - t0)


# ------------------------------------------------------------------ #
# sharded <-> flat host vectors
# ------------------------------------------------------------------ #
def shard_vector(dg: DGraph, x: np.ndarray, fill=0) -> np.ndarray:
    """Flat global (n,) -> sharded (P, n_loc_max) (padding = fill).

    A scatter (host value distributed *out* to shards), so it is not part
    of the instrumented gather API.
    """
    out = np.full((dg.nparts, dg.n_loc_max), fill, dtype=np.asarray(x).dtype)
    for p in range(dg.nparts):
        lo, hi = dg.vtxdist[p], dg.vtxdist[p + 1]
        out[p, :hi - lo] = x[lo:hi]
    return out


def _raster_flat(dg: DGraph, xs: np.ndarray) -> np.ndarray:
    """Sharded (P, n_loc_max) -> flat (n,) without touching the gather log.

    Internal staging primitive for the structure rebuilds; user-facing
    centralization must go through ``unshard_vector`` so it is counted.
    """
    xs = np.asarray(xs)
    li = np.arange(dg.n_loc_max)
    keep = (li[None, :] < dg.n_loc[:, None]).reshape(-1)
    return xs.reshape(dg.nparts * dg.n_loc_max, *xs.shape[2:])[keep]


def unshard_vector(dg: DGraph, xs: np.ndarray) -> np.ndarray:
    """Gather a sharded (P, n_loc_max) vector into a flat global (n,).

    One of the two instrumented centralizing gathers (with ``to_host``);
    the gather-free pipeline only applies it to sub-threshold objects.
    """
    _note_gather("unshard_vector", dg.n_global)
    return _raster_flat(dg, xs)


def shard_gids(dg: DGraph) -> np.ndarray:
    """(P, n_loc_max) global vertex id per local slot (-1 on padding)."""
    li = np.arange(dg.n_loc_max, dtype=np.int64)
    gid = dg.vtxdist[:-1, None] + li[None, :]
    return np.where(li[None, :] < dg.n_loc[:, None], gid, -1)


def valid_mask(dg: DGraph) -> np.ndarray:
    """(P, n_loc_max) bool: True on real local slots, False on padding."""
    li = np.arange(dg.n_loc_max)
    return li[None, :] < dg.n_loc[:, None]


def pull_by_gid(dg: DGraph, values_sh: np.ndarray, gid: np.ndarray,
                fill=0) -> np.ndarray:
    """Owner-routed value pull: out[...] = values of vertices ``gid``.

    ``values_sh`` is a (P, n_loc_max) sharded vector on ``dg``'s layout;
    ``gid`` is any-shape global ids (< 0 yields ``fill``).  This is the
    host-side model of the paper's point-to-point value fetch (the same
    owner lookup the halo exchange performs on device); data volume is
    O(len(gid)) words, independent of graph size.
    """
    gid = np.asarray(gid, dtype=np.int64)
    ok = (gid >= 0) & (gid < dg.n_global)
    gsafe = np.clip(gid, 0, max(dg.n_global - 1, 0))
    owner = np.searchsorted(dg.vtxdist, gsafe, side="right") - 1
    owner = np.clip(owner, 0, dg.nparts - 1)
    li = np.clip(gsafe - dg.vtxdist[owner], 0, dg.n_loc_max - 1)
    out = np.asarray(values_sh)[owner, li]
    return np.where(ok, out, fill)


def scatter_by_gid(dg: DGraph, target_sh: np.ndarray, gid: np.ndarray,
                   vals: np.ndarray) -> np.ndarray:
    """Owner-routed value push: write ``vals`` at vertices ``gid``.

    The inverse of ``pull_by_gid``: returns a copy of ``target_sh``
    (a (P, n_loc_max) sharded vector on ``dg``'s layout) with
    ``vals[k]`` written to the owner slot of ``gid[k]`` (negative ids
    skipped).  Models the project-back message of band refinement; data
    volume is O(len(gid)) words.
    """
    gid = np.asarray(gid, dtype=np.int64).reshape(-1)
    vals = np.asarray(vals).reshape(-1)
    ok = (gid >= 0) & (gid < dg.n_global)
    gid, vals = gid[ok], vals[ok]
    owner = np.searchsorted(dg.vtxdist, gid, side="right") - 1
    out = np.asarray(target_sh).copy()
    out[owner, gid - dg.vtxdist[owner]] = vals
    return out


def reshard_vector(src_dg: DGraph, dst_dg: DGraph, xs: np.ndarray,
                   fill=0) -> np.ndarray:
    """Move a sharded vector between two layouts of the *same* vertex set.

    Used when fold-dup rejoins: the winning duplicate's part vector lives
    on the folded layout and is pulled back onto the full group's layout.
    """
    assert src_dg.n_global == dst_dg.n_global
    return pull_by_gid(src_dg, xs, shard_gids(dst_dg), fill=fill)


# ------------------------------------------------------------------ #
# boundary masks + deterministic coloring (alternating-color schedule)
# ------------------------------------------------------------------ #
def np_hash_mix(x: np.ndarray, *salts: int) -> np.ndarray:
    """lowbias32 chain on int arrays (numpy mirror of matching.hash_mix).

    Every shard evaluates the same pure function of global ids alone, so
    symmetric rules (conflict-repair losers, boundary colors) need no
    extra messages — the same argument as the matching protocol's coins.
    """
    def lb(v):
        v = v ^ (v >> np.uint32(16))
        v = v * np.uint32(0x7FEB352D)
        v = v ^ (v >> np.uint32(15))
        v = v * np.uint32(0x846CA68B)
        return v ^ (v >> np.uint32(16))

    h = np.full(np.shape(x), 0x9E3779B9, dtype=np.uint32)
    for v in (x,) + salts:
        v = np.asarray(v).astype(np.uint32)
        h = lb(h ^ (v * np.uint32(0x85EBCA6B) + np.uint32(1)))
    return h


def boundary_mask(dg: DGraph) -> np.ndarray:
    """(P, n_loc_max) bool: local vertices with ≥ 1 cross-shard edge.

    A vertex is *boundary* when any ELL slot addresses the ghost ring
    (compact index ≥ n_loc_max).  Interior vertices can never create a
    cross-shard 0–1 edge, so refinement schedules only need to gate the
    boundary set.
    """
    return (dg.nbr_gst >= dg.n_loc_max).any(axis=2) & valid_mask(dg)


def color_by_gid(dg: DGraph, salt: int = 0, exchange: bool = True
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Deterministic two-coloring of vertices by gid hash (§3.3 schedule).

    Returns ``(hash_ext, color_ext)``, both (P, n_loc_max + n_ghost_max):
    the full uint32 hash (for tiebreaks on monochromatic edges) and the
    color (hash & 1, int8; -1 on padding) for every local slot *and* its
    ghost ring.  Local colors are computed from ``shard_gids``; ghost
    colors are the same pure hash of ``ghost_gid``, so owner and
    neighbor always agree with no messages.  With ``exchange`` the ghost
    colors are additionally halo-exchanged from the owners and
    cross-checked against the local recomputation — callers that
    re-color every round (the alternating-color band schedule rotates
    the salt to avoid starving tiebreak losers) validate the first
    coloring this way and skip the exchange for the rest, keeping the
    per-round exchange budget flat.
    """
    gid = shard_gids(dg)
    h_loc = np_hash_mix(np.maximum(gid, 0), salt & 0x7FFFFFFF)
    h_gst = np_hash_mix(np.maximum(dg.ghost_gid, 0), salt & 0x7FFFFFFF)
    hash_ext = np.concatenate([h_loc, h_gst], axis=1)
    col_loc = np.where(gid >= 0, (h_loc & 1).astype(np.int32), -1)
    gok = dg.ghost_gid >= 0
    if exchange:
        col_ext = np.asarray(halo_exchange_fn(dg)(col_loc))
        assert np.array_equal(np.where(gok, col_ext[:, dg.n_loc_max:], 0),
                              np.where(gok, h_gst & 1, 0)), \
            "halo-exchanged ghost colors disagree with the gid hash"
    color_ext = np.concatenate(
        [col_loc, np.where(gok, (h_gst & 1).astype(np.int32), -1)],
        axis=1).astype(np.int8)
    return hash_ext, color_ext


# ------------------------------------------------------------------ #
# structure rebuilds (host-modelled Alltoallv; DESIGN.md §4)
# ------------------------------------------------------------------ #
def dgraph_arcs(dg: DGraph) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Flat directed arc triples (src_gid, dst_gid, w) of the structure.

    The staging form every rebuild routes through; both directions of
    each undirected edge are present (ELL rows are symmetric).
    """
    nlm = dg.n_loc_max
    p, li, slot = np.nonzero(dg.nbr_gst >= 0)
    c = dg.nbr_gst[p, li, slot].astype(np.int64)
    src = dg.vtxdist[p] + li
    loc = c < nlm
    dst = np.where(loc, dg.vtxdist[p] + c,
                   dg.ghost_gid[p, np.maximum(c - nlm, 0)])
    w = dg.ewgt_gst[p, li, slot].astype(np.int64)
    return src, dst, w


def to_host(dg: DGraph) -> Graph:
    """Gather the distributed structure back into one centralized Graph.

    The §3.1 centralization step: below the sequential threshold the
    subgraph is gathered onto one process and ordered there.  Instrumented
    (see ``track_gathers``): the gather-free pipeline only calls this on
    sub-threshold subgraphs, coarsest graphs, and band graphs.
    """
    _note_gather("to_host", dg.n_global)
    src, dst, w = dgraph_arcs(dg)
    keep = src < dst                      # one direction; from_edges mirrors
    vwgt = _raster_flat(dg, dg.vwgt)
    return Graph.from_edges(dg.n_global,
                            np.stack([src[keep], dst[keep]], 1),
                            vwgt=vwgt, ewgt=w[keep])


def dgraph_induced(dg: DGraph, keep_sh: np.ndarray,
                   nparts: Optional[int] = None,
                   payloads: Sequence[np.ndarray] = (),
                   fills: Sequence = (),
                   bucket: bool = True
                   ) -> Tuple[DGraph, List[np.ndarray]]:
    """Distributed induced subgraph (paper §3.1, gather-free form).

    Args:
      keep_sh: (P, n_loc_max) bool mask of kept vertices (padding slots
        ignored).
      nparts: target shard count.  ``None`` keeps every kept vertex on its
        current owner (in-place extraction — the band path); an integer
        redistributes onto balanced blocks over that many shards (the
        paper folds each separated part onto its child process group).
      payloads: per-vertex (P, n_loc_max) arrays (e.g. original-id
        vectors) to carry onto the new layout.
      fills: padding fill value per payload (default 0).

    Kept vertices are renumbered by ascending global id, so the induced
    numbering is independent of the shard layout; new ownership ranges
    come from a prefix sum over per-shard keep counts (the offset
    exchange of the paper's redistribution).  Returns the sub-DGraph and
    the payloads mapped onto its layout.
    """
    keep = np.asarray(keep_sh, dtype=bool) & valid_mask(dg)
    counts = keep.sum(axis=1).astype(np.int64)
    n_new = int(counts.sum())
    if nparts is None:
        new_vtxdist = np.concatenate([[0], np.cumsum(counts)])
    else:
        new_vtxdist = np.linspace(0, n_new, nparts + 1).astype(np.int64)

    # rank kept vertices in shard-major raster order == ascending gid
    flatk = keep.reshape(-1)
    newid_flat = -np.ones(dg.n_global, dtype=np.int64)
    old_gid = shard_gids(dg).reshape(-1)[flatk]          # ascending
    newid_flat[old_gid] = np.arange(n_new)

    src, dst, w = dgraph_arcs(dg)
    ns, nd = newid_flat[src], newid_flat[dst]
    ka = (ns >= 0) & (nd >= 0)
    vwgt_new = dg.vwgt.reshape(-1)[flatk]
    sub = _build_dgraph(new_vtxdist, ns[ka], nd[ka], w[ka], vwgt_new,
                        bucket=bucket)
    mapped = []
    for i, pay in enumerate(payloads):
        fill = fills[i] if i < len(fills) else 0
        flat = np.asarray(pay).reshape(-1)[flatk]        # by new gid
        mapped.append(shard_vector(sub, flat, fill=fill))
    return sub, mapped


def dgraph_fold(dg: DGraph, bucket: bool = True) -> DGraph:
    """Fold the structure onto ⌈P/2⌉ shards (paper §3.2).

    Adjacent shard pairs merge (ownership ranges stay contiguous); global
    vertex ids are unchanged, so sharded vectors move between the two
    layouts with ``reshard_vector``.  Each fold-dup half runs an
    independent multilevel instance on (a duplicate of) the folded
    structure.
    """
    new_vtxdist = np.concatenate([dg.vtxdist[:-1:2], dg.vtxdist[-1:]])
    src, dst, w = dgraph_arcs(dg)
    vwgt = _raster_flat(dg, dg.vwgt)
    return _build_dgraph(new_vtxdist, src, dst, w, vwgt, bucket=bucket)


def dgraph_coarsen(dg: DGraph, match_sh: np.ndarray,
                   bucket: bool = True) -> Tuple[DGraph, np.ndarray]:
    """Distributed coarse-graph build from a sharded matching (§3.2).

    ``match_sh`` is (P, n_loc_max) mate global ids (self for singletons,
    as ``distributed_matching(..., flat=False)`` returns).  Each coarse
    vertex lives on the owner of its *representative* (min endpoint of
    the matched pair), so no vertex migrates at a coarsening step; coarse
    ownership ranges are the prefix sum of per-shard representative
    counts (identical to ``coarsen.coarse_vtxdist``), and the coarse
    numbering matches the centralized ``coarsen_once`` bit-for-bit.

    Returns ``(coarse_dg, cmap_sh)`` with cmap_sh[p, i] = coarse global
    id of fine local vertex i on shard p (-1 on padding).
    """
    gid = shard_gids(dg)
    valid = gid >= 0
    match = np.where(valid, np.asarray(match_sh, dtype=np.int64), -1)
    match = np.where(valid & (match >= 0) & (match < dg.n_global),
                     match, gid)
    rep = np.minimum(gid, match)
    is_rep = valid & (rep == gid)
    counts = is_rep.sum(axis=1).astype(np.int64)
    cvtxdist = np.concatenate([[0], np.cumsum(counts)])

    crank = (np.cumsum(is_rep.reshape(-1)) - 1).reshape(is_rep.shape)
    cmap_rep = np.where(is_rep, crank, np.int64(-1))
    # non-representatives read their mate's coarse id from its owner (the
    # mate is always the representative: rep = min of the pair)
    cmap_mate = pull_by_gid(dg, cmap_rep, match, fill=-1)
    cmap_sh = np.where(is_rep, cmap_rep, cmap_mate)
    assert int((cmap_sh[valid] < 0).sum()) == 0, \
        "match_sh is not an involution (mate's mate differs); pass a " \
        "matching from distributed_matching or repair symmetry first"
    cmap_sh = np.where(valid, cmap_sh, -1)

    cmap_flat = cmap_sh.reshape(-1)[valid.reshape(-1)]   # by fine gid
    nc = int(cvtxdist[-1])
    cvwgt = np.zeros(nc, dtype=np.int64)
    np.add.at(cvwgt, cmap_flat, _raster_flat(dg, dg.vwgt))
    src, dst, w = dgraph_arcs(dg)
    cs, cd = cmap_flat[src], cmap_flat[dst]
    ka = cs != cd                        # drop collapsed pairs
    cdg = _build_dgraph(cvtxdist, cs[ka], cd[ka], w[ka], cvwgt,
                        bucket=bucket)
    return cdg, cmap_sh


# ------------------------------------------------------------------ #
# lane-stacked halo exchange
# ------------------------------------------------------------------ #
def dgraph_bucket(dg: DGraph) -> Tuple[int, int, int, int]:
    """Jit bucket of a DGraph: ``(nparts, n_loc_max, dmax, n_ghost_max)``.

    Same-bucket graphs share compiled collectives AND may lane-stack into
    one launch (``distribute(bucket=True)`` pads shard shapes to powers
    of two precisely so sibling subgraphs of a recursion land together).
    """
    return (dg.nparts, dg.n_loc_max, dg.nbr_gst.shape[2],
            dg.ghost_gid.shape[1])


def _lane_pad(arrs: Sequence[np.ndarray]) -> Tuple[np.ndarray, int]:
    """Stack per-lane arrays, padding the lane axis to a power of two.

    Padding lanes duplicate lane 0 (real, discarded work — no garbage
    values reach reductions) so the jit cache sees O(log L) lane counts
    instead of one entry per frontier width.  Returns ``(stacked, L)``
    with L the real lane count.
    """
    L = len(arrs)
    pad = pow2(L, 1) - L
    return np.stack(list(arrs) + [arrs[0]] * pad), L


def _halo_gather(x, gids, vtxdist):
    """Lane-stacked per-shard halo body: ONE fused all_gather, all lanes.

    ``x`` (L, n_loc_max) this shard's values per lane; ``gids`` (L, G)
    per-lane ghost manifests; ``vtxdist`` (L, P+1) per-lane ranges.
    Returns (L, n_loc_max + G).  Shared by the standalone halo launch,
    the BFS sweep and the matching protocol (all run inside
    ``shard_map`` over the parts axis).
    """
    allx = jax.lax.all_gather(x, "parts")            # (P, L, n_loc_max)
    owner = jnp.clip(
        jax.vmap(functools.partial(jnp.searchsorted, side="right"))(
            vtxdist, gids) - 1, 0, allx.shape[0] - 1)
    local = jnp.clip(gids - jnp.take_along_axis(vtxdist, owner, axis=1),
                     0, allx.shape[2] - 1)
    lane = jnp.arange(x.shape[0])[:, None]
    vals = jnp.where(gids >= 0, allx[owner, lane, local], 0)
    return jnp.concatenate([x, vals], axis=1)


def _halo_stack_jit(nparts: int, n_loc_max: int, n_ghost_max: int,
                    lanes: int, dtype: str):
    mesh = make_parts_mesh(nparts)

    def body(x, gids, vtxdist):
        return _halo_gather(x[:, 0], gids[:, 0], vtxdist)[:, None]

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, "parts", None),
                                 P(None, "parts", None), P(None, None)),
                       out_specs=P(None, "parts", None))
    return jax.jit(fn)


def halo_exchange_stacked(dgs: Sequence[DGraph],
                          xs: Sequence[np.ndarray],
                          tags: Optional[Sequence] = None
                          ) -> List[np.ndarray]:
    """Halo-exchange many same-bucket graphs in ONE shard_map launch.

    ``xs[i]`` is graph i's (P, n_loc_max) sharded vector (one dtype for
    the whole stack); returns the (P, n_loc_max + n_ghost_max) extended
    vectors.  Lane i's result is bit-identical to a singleton exchange
    on ``dgs[i]`` — the gather indices are per-lane, the one fused
    ``all_gather`` only amortizes launch latency.  ``tags`` (optional,
    one per lane) records each lane's originating request in the launch
    metadata — the wave router's cross-request attribution.
    """
    key = dgraph_bucket(dgs[0])
    assert all(dgraph_bucket(d) == key for d in dgs), \
        "halo_exchange_stacked needs same-bucket graphs"
    nparts, nlm, _, G = key
    xs = [np.asarray(x) for x in xs]
    assert all(x.dtype == xs[0].dtype for x in xs)
    x_st, L = _lane_pad(xs)
    gid_st, _ = _lane_pad([d.ghost_gid.astype(np.int32) for d in dgs])
    vtx_st, _ = _lane_pad([d.vtxdist.astype(np.int32) for d in dgs])
    jkey = ("dhalo", nparts, nlm, G, x_st.shape[0], str(x_st.dtype))
    fn = _JIT_CACHE.get(jkey, lambda: _halo_stack_jit(
        nparts, nlm, G, x_st.shape[0], str(x_st.dtype)))
    out = obs.timed_dispatch(
        "halo", "dhalo", jkey,
        lambda: np.asarray(fn(jnp.asarray(x_st), jnp.asarray(gid_st),
                              jnp.asarray(vtx_st))),
        lanes=L, lanes_pad=x_st.shape[0], bucket=key)
    _note_launch("dhalo", nparts, L, x_st.shape[0], key[1:], 1,
                 x_st.shape[0] * nparts * nlm,
                 **({"tags": list(tags)} if tags is not None else {}))
    for _ in range(L):                   # per-work sync budget (see doc)
        _note_halo(nparts * nlm)
    return [out[i] for i in range(L)]


def halo_exchange_fn(dg: DGraph):
    """Returns halo(x (P, n_loc_max)) -> (P, n_loc_max + n_ghost_max).

    The one-lane convenience wrapper over ``halo_exchange_stacked``; the
    underlying jitted collective is cached per (bucket, lane count,
    dtype) and takes the ghost manifest / ranges as traced arguments, so
    it is reused by every same-bucket graph.
    """
    def halo(x):
        return halo_exchange_stacked([dg], [x])[0]
    return halo


def halo_reference(dg: DGraph, x: np.ndarray) -> np.ndarray:
    """Host oracle for tests."""
    Pn, G = dg.ghost_gid.shape
    out = np.zeros((Pn, dg.n_loc_max + G), dtype=x.dtype)
    flat = np.zeros(dg.vtxdist[-1], dtype=x.dtype)
    for p in range(Pn):
        lo, hi = dg.vtxdist[p], dg.vtxdist[p + 1]
        flat[lo:hi] = x[p, :hi - lo]
    for p in range(Pn):
        out[p, :dg.n_loc_max] = x[p]
        for k, gid in enumerate(dg.ghost_gid[p]):
            if gid >= 0:
                out[p, dg.n_loc_max + k] = flat[gid]
    return out


# ------------------------------------------------------------------ #
# distributed band-BFS (lane-stacked)
# ------------------------------------------------------------------ #
def _bfs_stack_jit(nparts: int, n_loc_max: int, dmax: int, n_ghost_max: int,
                   width: int, lanes: int):
    from repro.kernels.ops import ell_relax_step
    mesh = make_parts_mesh(nparts)

    def body(nbr, src, gids, vtxdist):
        nbr, src, gids = nbr[:, 0], src[:, 0], gids[:, 0]
        BIG = jnp.int32(2 ** 30)
        dist = jnp.where(src != 0, 0, BIG).astype(jnp.int32)

        def step(dist, _):
            ext = _halo_gather(dist, gids, vtxdist)
            return jnp.minimum(dist, ell_relax_step(nbr, ext, BIG)), None

        dist, _ = jax.lax.scan(step, dist, None, length=width)
        return dist[:, None]

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, "parts", None, None),
                                 P(None, "parts", None),
                                 P(None, "parts", None), P(None, None)),
                       out_specs=P(None, "parts", None))
    return jax.jit(fn)


def distributed_bfs_stacked(dgs: Sequence[DGraph],
                            srcs: Sequence[np.ndarray],
                            width: int,
                            tags: Optional[Sequence] = None
                            ) -> List[np.ndarray]:
    """Band-distance sweeps of many same-bucket graphs in ONE launch.

    One fused ``all_gather`` per relaxation step serves every lane; the
    per-lane min-plus relaxations (``ell_relax_step`` with a lane axis)
    never mix lanes, so each lane equals its singleton sweep bit-for-bit.
    ``tags`` attributes lanes to requests (see ``halo_exchange_stacked``).
    """
    key = dgraph_bucket(dgs[0])
    assert all(dgraph_bucket(d) == key for d in dgs), \
        "distributed_bfs_stacked needs same-bucket graphs"
    nparts, nlm, dmax, G = key
    nbr_st, L = _lane_pad([d.nbr_gst for d in dgs])
    src_st, _ = _lane_pad([np.asarray(s, np.int32) for s in srcs])
    gid_st, _ = _lane_pad([d.ghost_gid.astype(np.int32) for d in dgs])
    vtx_st, _ = _lane_pad([d.vtxdist.astype(np.int32) for d in dgs])
    jkey = ("dbfs", nparts, nlm, dmax, G, width, nbr_st.shape[0])
    fn = _JIT_CACHE.get(jkey, lambda: _bfs_stack_jit(
        nparts, nlm, dmax, G, width, nbr_st.shape[0]))
    dist = obs.timed_dispatch(
        "bfs", "dbfs", jkey,
        lambda: np.asarray(fn(jnp.asarray(nbr_st), jnp.asarray(src_st),
                              jnp.asarray(gid_st), jnp.asarray(vtx_st))),
        lanes=L, lanes_pad=nbr_st.shape[0], bucket=key, width=width)
    _note_launch("dbfs", nparts, L, nbr_st.shape[0], key[1:], width,
                 width * nbr_st.shape[0] * nparts * nlm,
                 **({"tags": list(tags)} if tags is not None else {}))
    return [dist[i] for i in range(L)]


def distributed_bfs(dg: DGraph, src_mask: np.ndarray,
                    width: int) -> np.ndarray:
    """Band-graph distance sweep (§3.3) on the distributed structure: one
    halo exchange per relaxation — the paper's 'spreading distance
    information from all of the separator vertices, using our halo exchange
    routine'.  One-lane wrapper over ``distributed_bfs_stacked``."""
    return distributed_bfs_stacked([dg], [src_mask], width)[0]


# ------------------------------------------------------------------ #
# distributed heavy-edge matching (paper §3.2, lane-stacked)
# ------------------------------------------------------------------ #
def _matching_stack_jit(nparts: int, n_loc_max: int, dmax: int,
                        n_ghost_max: int, rounds: int, lanes: int,
                        cap: int = 0):
    """``cap`` > 0 compacts the per-round proposal gather: each shard
    scatters its (tgt, w, gid) proposals into (L, cap) compact buffers
    before the ``all_gather``, so the gathered width is the proposer
    *bound*, not the dense ``n_loc_max``.  The proposer gid travels as
    an explicit third buffer (the dense layout recovers it from the row
    position).  With a cap that bounds every round's true proposal
    count the winner tables — segment max/min over the same (score,
    gid, target) set — are bit-identical to the dense protocol's.
    ``cap`` = 0 keeps the dense positional layout."""
    mesh = make_parts_mesh(nparts)
    INT_MAX = jnp.iinfo(jnp.int32).max
    nseg = nparts * n_loc_max + 1       # winner-table slots (+1 dump)

    def body(nbr, ew, gids, vtxdist, nloc, seeds):
        nbr, ew, gids, nloc = nbr[:, 0], ew[:, 0], gids[:, 0], nloc[:, 0]
        L = nbr.shape[0]
        lane = jnp.arange(L)
        pidx = jax.lax.axis_index("parts")
        lo = vtxdist[:, pidx]                             # (L,)
        li = jnp.arange(n_loc_max, dtype=jnp.int32)
        valid_loc = li[None, :] < nloc[:, None]
        my_gid = jnp.where(valid_loc, lo[:, None] + li[None, :], -1)
        ext_gid = jnp.concatenate([my_gid, gids], axis=1)
        valid_e = nbr >= 0
        nb = jnp.where(valid_e, nbr, 0)                   # (L, nlm, d)
        ewf = ew.astype(jnp.float32)
        # proposer gid of every (shard, row) of the gathered proposal
        # buffers; every shard can compute it from vtxdist alone
        prop_gid_flat = (vtxdist[:, :nparts, None]
                         + li[None, None, :]).reshape(L, -1)

        def gather_flat(x):
            return jnp.moveaxis(jax.lax.all_gather(x, "parts"),
                                0, 1).reshape(x.shape[0], -1)

        def ext_at(ext, idx):
            # per-lane gather: ext (L, m), idx (L, n, d) -> (L, n, d)
            return jnp.take_along_axis(
                ext, idx.reshape(L, -1), axis=1).reshape(idx.shape)

        def owner_loc(t):
            # (L, K) global ids -> (owner shard, local slot) per lane
            tsafe = jnp.maximum(t, 0)
            ow = jnp.clip(
                jax.vmap(functools.partial(jnp.searchsorted, side="right"))(
                    vtxdist, tsafe) - 1, 0, nparts - 1)
            lc = jnp.clip(tsafe - jnp.take_along_axis(vtxdist, ow, axis=1),
                          0, n_loc_max - 1)
            return ow, lc

        def round_fn(match, r):
            unmatched = (match < 0) & valid_loc
            ext_unm = _halo_gather(unmatched.astype(jnp.int32), gids,
                                   vtxdist) != 0
            # hash coin: any shard can evaluate any vertex's side locally
            is_prop_ext = (hash_mix(ext_gid, r, seeds[:, None]) & 1) == 1
            # --- propose: heaviest unmatched acceptor neighbor
            tgt_slots = ext_at(ext_gid, nb)               # (L, nlm, d)
            cand = (valid_e & ext_at(ext_unm, nb) & ~ext_at(is_prop_ext, nb)
                    & (tgt_slots >= 0))
            tie = hash_unit(my_gid[:, :, None], tgt_slots, r + 17)
            score = jnp.where(cand, ewf + tie, -jnp.inf)
            slot = jnp.argmax(score, axis=2)[:, :, None]
            has = (jnp.any(cand, axis=2) & unmatched
                   & is_prop_ext[:, :n_loc_max])
            prop_tgt = jnp.where(
                has, jnp.take_along_axis(tgt_slots, slot, 2)[..., 0], -1)
            prop_w = jnp.where(
                has, jnp.take_along_axis(ewf, slot, 2)[..., 0], 0.0)

            # --- grant: ONE gather of the proposals; every shard then
            # derives the same per-acceptor winner table locally (pure
            # function of the gathered buffers), so no grant buffer is
            # ever gathered back — the notify leg costs zero words
            if cap:
                # compact the ≤ cap live proposals to the row front and
                # gather (tgt, w, gid) at width cap instead of n_loc_max.
                # pos ≥ cap (a non-proposing row, or overflow past the
                # bound — impossible by construction) drops.
                pos = jnp.where(
                    has, jnp.cumsum(has.astype(jnp.int32), axis=1) - 1,
                    cap)
                lane2 = jnp.broadcast_to(lane[:, None], pos.shape)
                ctgt = jnp.full((L, cap), -1, jnp.int32) \
                    .at[lane2, pos].set(prop_tgt, mode="drop")
                cw = jnp.zeros((L, cap), jnp.float32) \
                    .at[lane2, pos].set(prop_w, mode="drop")
                cgid = jnp.full((L, cap), -1, jnp.int32) \
                    .at[lane2, pos].set(my_gid, mode="drop")
                allt = gather_flat(ctgt)                  # (L, P·cap)
                allw = gather_flat(cw)
                allg = gather_flat(cgid)
            else:
                allt = gather_flat(prop_tgt)              # (L, P·nlm)
                allw = gather_flat(prop_w)
                allg = prop_gid_flat
            okp = allt >= 0
            ow, lc = owner_loc(allt)
            seg = jnp.where(okp, ow * n_loc_max + lc, nseg - 1)
            seg_l = (lane[:, None] * nseg + seg).reshape(-1)
            gsc = allw + hash_unit(allg, allt, r + 31)
            gsc = jnp.where(okp, gsc, -jnp.inf).reshape(-1)
            best = jax.ops.segment_max(gsc, seg_l, num_segments=L * nseg)
            is_best = okp.reshape(-1) & (gsc >= best[seg_l])
            winner = jax.ops.segment_min(
                jnp.where(is_best, allg.reshape(-1), INT_MAX),
                seg_l, num_segments=L * nseg).reshape(L, nseg)

            # acceptors: my slots of the winner table
            win_mine = jax.lax.dynamic_slice_in_dim(
                winner, pidx * n_loc_max, n_loc_max, axis=1)
            can_accept = unmatched & ~is_prop_ext[:, :n_loc_max]
            grant = jnp.where(can_accept & (win_mine < INT_MAX),
                              win_mine, -1)
            # proposers: the winner of the slot they proposed to (a
            # proposal existing implies the target could accept this
            # round — ``cand`` checked the exchanged unmatched mask and
            # the acceptor-side coin, the same values the owner sees)
            ow_p, lc_p = owner_loc(prop_tgt)
            win_t = jnp.take_along_axis(winner, ow_p * n_loc_max + lc_p,
                                        axis=1)
            got = (prop_tgt >= 0) & (win_t == my_gid)
            match = jnp.where(got, prop_tgt, match)
            match = jnp.where(grant >= 0, grant, match)
            return match, None

        # the rounds make the carry per-shard; the initial fill is not,
        # so mark it varying over ``parts`` for the scan's type check
        match0 = jax.lax.pcast(jnp.full((L, n_loc_max), -1, jnp.int32),
                               "parts", to="varying")
        match, _ = jax.lax.scan(round_fn, match0,
                                jnp.arange(rounds, dtype=jnp.int32))
        return match[:, None]

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, "parts", None, None),
                                 P(None, "parts", None, None),
                                 P(None, "parts", None), P(None, None),
                                 P(None, "parts"), P(None)),
                       out_specs=P(None, "parts", None))
    return jax.jit(fn)


def _match_proposal_cap(dgs: Sequence[DGraph], nlm: int) -> int:
    """Lossless per-shard proposal bound of a matching lane stack.

    A vertex can propose in *any* round only if it is valid and has at
    least one valid ELL edge (``cand`` requires one), so the max over
    shards and lanes of that count bounds every round's true proposal
    width — compaction at this cap never drops a proposal, keeping the
    compact protocol bit-identical to the dense one regardless of which
    lanes happen to share the launch.  Quantized up to sub-pow2 steps
    (``max(8, nlm // 8)``) so the jit key space stays coarse.
    """
    k = 1
    for d in dgs:
        can = (shard_gids(d) >= 0) & (d.nbr_gst >= 0).any(axis=2)
        k = max(k, int(can.sum(axis=1).max()))
    q = max(8, nlm // 8)
    return min(nlm, -(-k // q) * q)


def distributed_matching_stacked(dgs: Sequence[DGraph],
                                 seeds: Sequence[int],
                                 rounds: int = 8,
                                 tags: Optional[Sequence] = None
                                 ) -> List[np.ndarray]:
    """Match many same-bucket graphs in ONE shard_map launch.

    Returns, per graph, the sharded (P, n_loc_max) mate global ids
    (``flat=False`` contract: -1→self masking and owner-routed symmetry
    repair applied).  Coins, tiebreaks and the per-lane grant reductions
    are functions of each lane's own (gids, seed) alone, so lane i's
    matching is bit-identical to ``distributed_matching(dgs[i], ...)``.

    When compaction is on (``set_match_compact`` / RouterConfig) and the
    proposer bound is small enough to pay (3·cap < 2·n_loc_max, i.e. the
    compact round — halo + 3 cap-wide buffers — beats the dense round's
    3 n_loc_max-wide buffers), the proposal gather runs at the lossless
    cap of ``_match_proposal_cap``; the launch record then carries
    ``cap`` and the counterfactual ``words_dense``.  ``tags`` attributes
    lanes to requests (see ``halo_exchange_stacked``).
    """
    key = dgraph_bucket(dgs[0])
    assert all(dgraph_bucket(d) == key for d in dgs), \
        "distributed_matching_stacked needs same-bucket graphs"
    nparts, nlm, dmax, G = key
    nbr_st, L = _lane_pad([d.nbr_gst for d in dgs])
    ew_st, _ = _lane_pad([d.ewgt_gst.astype(np.int32) for d in dgs])
    gid_st, _ = _lane_pad([d.ghost_gid.astype(np.int32) for d in dgs])
    vtx_st, _ = _lane_pad([d.vtxdist.astype(np.int32) for d in dgs])
    nloc_st, _ = _lane_pad([d.n_loc.astype(np.int32) for d in dgs])
    seed_st, _ = _lane_pad([np.int32(s & 0x7FFFFFFF) for s in seeds])
    cap = 0
    if _MATCH_COMPACT:
        c = _match_proposal_cap(dgs, nlm)
        if 3 * c < 2 * nlm:
            cap = c
    jkey = ("dmatch", nparts, nlm, dmax, G, rounds, nbr_st.shape[0], cap)
    fn = _JIT_CACHE.get(jkey, lambda: _matching_stack_jit(
        nparts, nlm, dmax, G, rounds, nbr_st.shape[0], cap))
    m = obs.timed_dispatch(
        "match", "dmatch", jkey,
        lambda: np.asarray(fn(jnp.asarray(nbr_st), jnp.asarray(ew_st),
                              jnp.asarray(gid_st), jnp.asarray(vtx_st),
                              jnp.asarray(nloc_st), jnp.asarray(seed_st))),
        lanes=L, lanes_pad=nbr_st.shape[0], bucket=key, rounds=rounds,
        cap=cap)
    # per dense round: unmatched-mask halo + proposal targets + proposal
    # weights (the grant gather-back of the pre-frontier protocol is
    # gone); a compact round gathers the halo at n_loc_max plus three
    # cap-wide buffers (targets, weights, proposer gids)
    words_dense = rounds * 3 * nbr_st.shape[0] * nparts * nlm
    words = (rounds * nbr_st.shape[0] * nparts * (nlm + 3 * cap)
             if cap else words_dense)
    _note_launch("dmatch", nparts, L, nbr_st.shape[0], key[1:], rounds,
                 words, cap=cap, words_dense=words_dense,
                 **({"tags": list(tags)} if tags is not None else {}))
    out = []
    for i, dg in enumerate(dgs):
        gid = shard_gids(dg)
        valid = gid >= 0
        m_sh = m[i].astype(np.int64)
        m_sh = np.where(valid & (m_sh >= 0) & (m_sh < dg.n_global),
                        m_sh, gid)
        # defensive symmetry repair (protocol is symmetric by
        # construction): each vertex checks its mate's mate via an
        # owner-routed pull
        mate_of_mate = pull_by_gid(dg, m_sh, m_sh, fill=-1)
        out.append(np.where(valid & (mate_of_mate == gid), m_sh, gid))
    return out


def distributed_matching(dg: DGraph, seed: int, rounds: int = 8,
                         flat: bool = True) -> np.ndarray:
    """Synchronous probabilistic heavy-edge matching across shards.

    The paper's request/grant protocol (§3.2) with the collectives of this
    file: each round, unmatched proposers pick their heaviest unmatched
    acceptor neighbor (ghosts included, via halo exchange of the unmatched
    mask); proposals are gathered once, and every shard derives the same
    per-acceptor winner table from the gathered buffers — acceptors grant
    from their slots, proposers read their target's slot, and both ends
    commit with **no grant gather-back** (the notify leg of the
    pre-frontier protocol cost a dense (P, n_loc_max) all_gather per
    round).  Coin flips and tiebreaks are hashes of (gid, round, seed),
    so every shard evaluates any vertex's state without extra messages —
    and the result is independent of the shard layout.

    With ``flat`` (legacy contract) the matching is gathered into a flat
    global (n,) array with match[v] = v for singletons — same contract as
    ``matching.heavy_edge_matching``.  With ``flat=False`` it stays
    sharded: (P, n_loc_max) mate global ids (-1 on padding), the form
    ``dgraph_coarsen`` consumes — no centralization at any size.
    One-lane wrapper over ``distributed_matching_stacked``.
    """
    m_sh = distributed_matching_stacked([dg], [seed], rounds)[0]
    if flat:
        return unshard_vector(dg, m_sh)
    return m_sh
