"""Ordering service: bucketed execution parity, batched kernels, cache,
end-to-end equivalence with the sequential driver."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.band import (BFSWork, bfs_distance, execute_bfs_works)
from repro.core.fm import FMWork, execute_fm_works, refine_parts
from repro.core.nd import NDConfig, nested_dissection
from repro.graphs import generators as G
from repro.kernels.ops import band_bfs_batch, sep_gain_batch
from repro.kernels.ref import bfs_multi_ref, sep_gain_multi_ref
from repro.service import OrderingService, order_batch
from repro.service.cache import FingerprintCache
from repro.service.fingerprint import graph_fingerprint, request_fingerprint


def _sep_work(g, seed):
    """A valid FM work: grown initial separator on g."""
    from repro.core.initsep import grow_part
    part = grow_part(g, seed)
    nbr, _ = g.to_ell()
    return FMWork(nbr=nbr, vwgt=g.vwgt, part=part,
                  locked=np.zeros(g.n, bool), seed=seed, k_inst=4)


# ------------------------------------------------------------------ #
# bucketed executors == singleton execution
# ------------------------------------------------------------------ #
def test_fm_bucketed_matches_singleton():
    works = [_sep_work(G.grid2d(11, 11), 0),
             _sep_work(G.grid2d(10, 12), 1),       # same bucket as above
             _sep_work(G.grid3d(5, 5, 5), 2),
             _sep_work(G.circuit(100, seed=4), 3)]
    together = execute_fm_works(works)
    alone = [execute_fm_works([w])[0] for w in works]
    for (pa, wa, ia), (pb, wb, ib) in zip(together, alone):
        assert np.array_equal(pa, pb)
        assert wa == wb and ia == ib


def test_refine_parts_unchanged_contract():
    g = G.grid2d(12, 12)
    from repro.core.initsep import grow_part
    part = grow_part(g, 5)
    nbr, _ = g.to_ell()
    out, sep_w, imb = refine_parts(nbr, g.vwgt, part,
                                   np.zeros(g.n, bool), 7)
    assert out.shape == (g.n,)
    assert sep_w == g.vwgt[out == 2].sum()


def test_bfs_bucketed_matches_singleton():
    gs = [G.grid2d(9, 9), G.grid2d(8, 10), G.grid3d(4, 4, 5)]
    works = []
    for i, g in enumerate(gs):
        nbr, _ = g.to_ell()
        src = np.zeros(g.n, bool)
        src[i] = True
        works.append(BFSWork(nbr=nbr, src=src, width=3))
    batched = execute_bfs_works(works)
    for w, dist in zip(works, batched):
        ref = np.asarray(bfs_distance(jnp.asarray(w.nbr),
                                      jnp.asarray(w.src), w.width))
        assert np.array_equal(np.minimum(dist, w.width + 1),
                              np.minimum(ref, w.width + 1))


# ------------------------------------------------------------------ #
# batched Pallas kernels == jnp oracles (interpret mode on CPU)
# ------------------------------------------------------------------ #
def test_bfs_kernel_matches_ref():
    rng = np.random.default_rng(0)
    L, n, d = 4, 64, 8
    nbr = rng.integers(-1, n, (L, n, d)).astype(np.int32)
    src = (rng.random((L, n)) < 0.08).astype(np.int32)
    got = np.asarray(band_bfs_batch(nbr, src, 3))
    want = np.asarray(bfs_multi_ref(jnp.asarray(nbr), jnp.asarray(src), 3))
    assert np.array_equal(got, want)


def test_gain_kernel_matches_ref():
    rng = np.random.default_rng(1)
    L, n, d = 3, 128, 8
    nbr = rng.integers(-1, n, (L, n, d)).astype(np.int32)
    vwgt = rng.integers(1, 6, (L, n)).astype(np.float32)
    part = rng.integers(0, 3, (L, n)).astype(np.int32)
    g0, g1 = sep_gain_batch(nbr, vwgt, part)
    r0, r1 = sep_gain_multi_ref(jnp.asarray(nbr), jnp.asarray(vwgt),
                                jnp.asarray(part))
    assert np.array_equal(np.asarray(g0), np.asarray(r0))
    assert np.array_equal(np.asarray(g1), np.asarray(r1))


def test_fm_pallas_gain_mode_bit_equal():
    w = _sep_work(G.grid2d(12, 12), 3)
    a = execute_fm_works([w], gain_mode="jnp")[0]
    b = execute_fm_works([w], gain_mode="pallas")[0]
    assert np.array_equal(a[0], b[0]) and a[1:] == b[1:]


# ------------------------------------------------------------------ #
# fingerprints + cache
# ------------------------------------------------------------------ #
def test_fingerprint_sensitivity():
    g = G.grid2d(6, 6)
    g2 = G.grid2d(6, 6)
    assert graph_fingerprint(g) == graph_fingerprint(g2)
    cfg = NDConfig()
    fp = request_fingerprint(g, 0, 4, cfg)
    assert request_fingerprint(g, 1, 4, cfg) != fp         # seed
    assert request_fingerprint(g, 0, 8, cfg) != fp         # nproc
    assert request_fingerprint(g, 0, 4, NDConfig(band_width=2)) != fp
    g3 = G.grid2d(6, 6)
    g3.vwgt = g3.vwgt.copy()
    g3.vwgt[0] = 7
    assert graph_fingerprint(g3) != graph_fingerprint(g)   # weights


def test_cache_put_does_not_freeze_caller():
    """Regression: put() used to setflags(write=False) on an aliasing view
    of the caller's array, freezing the submitter's permutation in place."""
    c = FingerprintCache(capacity=4)
    mine = np.arange(6)
    c.put("k", mine)
    assert mine.flags.writeable, "caller's array was frozen by the cache"
    mine[0] = 99                                  # must not raise
    got = c.get("k")
    assert got[0] == 0, "cache entry aliases the caller's array"
    assert not got.flags.writeable                # cached copy stays frozen


def test_cache_lru_and_counters():
    c = FingerprintCache(capacity=2)
    c.put("a", np.arange(3))
    c.put("b", np.arange(4))
    assert c.get("a") is not None                          # a now MRU
    c.put("c", np.arange(5))                               # evicts b
    assert c.get("b") is None
    assert c.get("a") is not None and c.get("c") is not None
    assert c.evictions == 1 and c.hits == 3 and c.misses == 1
    assert 0 < c.hit_rate < 1


# ------------------------------------------------------------------ #
# end to end: scheduler and service vs looped sequential driver
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def mixed_graphs():
    uniq = [G.grid2d(12, 12), G.grid3d(6, 6, 6), G.grid2d(15, 10),
            G.circuit(300, seed=3), G.grid2d(13, 11), G.rgg2d(250, seed=2),
            G.grid3d(5, 5, 6), G.grid2d(11, 14)]
    return uniq


def test_order_batch_matches_sequential(mixed_graphs):
    seeds = list(range(len(mixed_graphs)))
    batched = order_batch(mixed_graphs, seeds, 4)
    for g, s, perm in zip(mixed_graphs, seeds, batched):
        ref = nested_dissection(g, seed=s, nproc=4)
        assert np.array_equal(perm, ref)


def test_service_end_to_end(mixed_graphs):
    svc = OrderingService(cache_capacity=64)
    # ≥16 requests over mixed sizes, with duplicates in the stream
    reqs = []
    for rep in range(2):
        for i, g in enumerate(mixed_graphs):
            reqs.append(svc.submit(g, seed=i, nproc=4))
    assert len(reqs) == 16
    assert svc.poll(reqs[0]) is None                       # still queued
    resolved = svc.drain()
    assert len(resolved) == 16
    st = svc.stats()
    assert st["computed"] == 8                             # dedup coalesced
    # every request got the exact sequential-driver answer
    for i, rid in enumerate(reqs):
        res = svc.poll(rid)
        g, s = mixed_graphs[i % 8], i % 8
        assert np.array_equal(np.sort(res.perm), np.arange(g.n))
        ref = nested_dissection(g, seed=s, nproc=4)
        assert np.array_equal(res.perm, ref)
    # repeated submission afterwards is a cache hit, resolved immediately
    rid = svc.submit(mixed_graphs[0], seed=0, nproc=4)
    res = svc.poll(rid)
    assert res is not None and res.cached
    st = svc.stats()
    assert st["cache_hits"] >= 1
    assert st["p95_latency_ms"] >= st["p50_latency_ms"]
    assert st["orderings_per_sec"] > 0
    assert st["queue_depth"] == 0


def test_latency_split_queue_wait_vs_exec(mixed_graphs):
    """Drained requests report queue wait and execution separately: the
    end-to-end latency decomposes instead of conflating how long the
    request sat in the drain queue with how fast the batch ran."""
    import time
    svc = OrderingService()
    rid0 = svc.submit(mixed_graphs[0], seed=0, nproc=2)
    time.sleep(0.05)                    # measurable queue wait
    rid1 = svc.submit(mixed_graphs[1], seed=1, nproc=2)
    t0 = time.perf_counter()
    svc.drain()
    drain_s = time.perf_counter() - t0
    for rid in (rid0, rid1):
        res = svc.poll(rid)
        assert res.queue_wait_s >= 0 and res.exec_s > 0
        # wait + execution bound the end-to-end latency
        assert res.latency_s >= res.queue_wait_s
        assert res.latency_s >= res.exec_s
        # each request is billed its own share of the waves it rode,
        # which cannot exceed the wall time of the drain that ran them
        assert res.exec_s <= drain_s
    # rid0 waited through the sleep
    assert svc.poll(rid0).queue_wait_s >= 0.05
    # a cache hit has no queue wait — its latency IS the lookup
    rid2 = svc.submit(mixed_graphs[0], seed=0, nproc=2)
    res2 = svc.poll(rid2)
    assert res2.cached and res2.queue_wait_s == 0.0
    st = svc.stats()
    for key in ("p50_queue_wait_ms", "p95_queue_wait_ms",
                "p50_exec_ms", "p95_exec_ms"):
        assert key in st and st[key] >= 0
    assert st["p95_queue_wait_ms"] >= st["p50_queue_wait_ms"]
    assert st["p95_exec_ms"] >= st["p50_exec_ms"]


def test_service_deterministic_across_drains(mixed_graphs):
    g = mixed_graphs[1]
    svc1 = OrderingService()
    svc2 = OrderingService()
    r1 = svc1.submit(g, seed=9, nproc=2)
    r2 = svc2.submit(g, seed=9, nproc=2)
    svc1.drain()
    svc2.drain()
    assert np.array_equal(svc1.poll(r1).perm, svc2.poll(r2).perm)
