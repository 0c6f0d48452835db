"""Side rows: a band's anchors leave the FM neighbour table, bit for bit.

A locked row much wider than the rest of its table (the anchor of a band,
wired to a whole band layer) rides beside the table as a count vector
(``FMWork.split``, ``kernels.fm_fused``).  The tables that stay are 32 or
16 wide where the anchors made them 64-256.  Here:

* hoisted and fused (interpret) FM on the split table equal the oracle on
  the whole table — ``parts``, ``sep_w``, ``imb``, ``iters`` and
  ``last_better`` — on 27-point plane bands and circuit bands, and count
  the same ``pull_overflow`` as the hoisted path on the whole table;
* a move that pulls an anchor into the separator leaves the same gains
  with the split table as with the whole one;
* the split is read from locks and degrees alone: three wide locked rows,
  or a row wider than the anchors, keep the whole table; rows whose slots
  are not packed to the left keep their neighbours.
"""
import os
from collections import deque

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp  # noqa: E402

from repro.core.band import extract_band  # noqa: E402
from repro.core.fm import (FMWork, _prepare_lanes, _pulled_jnp,  # noqa: E402
                           fm_refine_multi)
from repro.graphs import generators as G  # noqa: E402
from repro.kernels.fm_fused import (SIDE_K, fm_fused_multi,  # noqa: E402
                                    fm_move_loop, fm_noise)
from repro.kernels.ref import fm_fused_ref  # noqa: E402
from repro.util import pow2  # noqa: E402
from test_fm_fused import _assert_bit_identical, _no_side  # noqa: E402


def _plane_part(k):
    """Sides of the plane x == k // 2 of a k^3 grid, the plane between."""
    x = np.arange(k ** 3) // (k * k)
    return np.where(x < k // 2, 0, np.where(x > k // 2, 1, 2)).astype(np.int8)


def _level_part(g):
    """Sides of the median BFS level from vertex 0, the level between."""
    nbr, _ = g.to_ell()
    dist = np.full(g.n, -1)
    dist[0] = 0
    queue = deque([0])
    while queue:
        u = queue.popleft()
        for x in nbr[u][nbr[u] >= 0]:
            if dist[x] < 0:
                dist[x] = dist[u] + 1
                queue.append(x)
    mid = int(np.median(dist[dist >= 0]))
    return np.where((dist >= 0) & (dist < mid), 0,
                    np.where(dist == mid, 2, 1)).astype(np.int8)


def _band_work(kind, size, width, k_inst=8, **kw):
    if kind == "plane":
        g = G.grid3d(size, size, size, stencil=27)
        part = _plane_part(size)
    else:
        g = G.circuit(size, seed=0)
        part = _level_part(g)
    band, bpart, locked, _ = extract_band(g, part, width=width)
    nbr, _ = band.to_ell()
    return FMWork(nbr=nbr, vwgt=band.vwgt, part=bpart, locked=locked,
                  seed=size + width, k_inst=k_inst, **kw)


def _stack(w, full_width=None):
    """The lane stack of ``w``: its eight FM inputs, and its side rows."""
    ln = _prepare_lanes(w, full_width)
    args = tuple(jnp.asarray(a) for a in (
        ln.nbr, ln.vwgt, ln.parts0, ln.locked, ln.keys, ln.eps,
        ln.max_moves, ln.n_pert))
    return args, (jnp.asarray(ln.side_id), jnp.asarray(ln.side_cnt))


#: band, widths, and a perturbation and balance slack under which lanes
#: pull anchors in ways that steer later moves: without the side rows'
#: update every case's moves or parts differ from the oracle's
SPLIT_CASES = [
    ("plane", 7, 1, 32, 64, 10_000, 0.3),
    ("plane", 7, 2, 32, 64, 10_000, 0.1),
    ("plane", 9, 1, 32, 128, 32, 0.3), ("plane", 9, 3, 32, 128, 10_000, 0.1),
    ("circuit", 400, 1, 16, 128, 32, 0.1),
    ("circuit", 400, 2, 16, 32, 32, 0.1)]


@pytest.mark.parametrize("kind,size,width,d_split,d_full,n_pert,eps",
                         SPLIT_CASES)
def test_split_table_equals_the_oracle_on_the_whole_table(
        kind, size, width, d_split, d_full, n_pert, eps):
    w = _band_work(kind, size, width, n_pert=n_pert, eps_frac=eps)
    full_width = pow2(w.nbr.shape[1], 8)
    assert (w.bucket_key()[1], full_width) == (d_split, d_full)
    split, side = _stack(w)
    whole, _ = _stack(w, full_width)
    n = split[0].shape[1]
    assert split[0].shape[2] == d_split and whole[0].shape[2] == d_full
    assert int((np.asarray(side[0]) < n).sum(axis=1).min()) >= 1
    hoisted = fm_refine_multi(*split, *side, passes=3, gain_mode="jnp")
    fused = fm_fused_multi(*split, *side, passes=3, interpret=True)
    nbr, vwgt, parts0, locked, keys, eps, mm, n_pert = whole
    oracle = fm_fused_ref(
        nbr, vwgt, parts0, locked, fm_noise(keys, n, 3),
        eps * vwgt.astype(jnp.float32).sum(axis=1), mm, n_pert, passes=3)
    unsplit = fm_refine_multi(*whole, *_no_side(*whole[1].shape), passes=3,
                              gain_mode="jnp")
    _assert_bit_identical(hoisted, oracle, "hoisted (split) vs oracle")
    _assert_bit_identical(fused, oracle, "fused (split) vs oracle")
    moves = np.asarray(hoisted[3])
    assert np.array_equal(moves, np.asarray(fused[3]))
    assert np.array_equal(moves[..., :2], np.asarray(oracle[3]))
    assert np.array_equal(moves, np.asarray(unsplit[3]))   # pull_overflow
    assert moves[..., 0].sum() > 0


def test_a_pulled_anchor_leaves_the_same_gains():
    """One move of a last-layer vertex to side 1 pulls anchor 0 into the
    separator: the split table's dense update and the whole table's row
    scatter leave the same gains, but for the anchor's own."""
    band, bpart, locked, _ = extract_band(
        G.grid3d(7, 7, 7, stencil=27), _plane_part(7), width=1)
    nbr, _ = band.to_ell()
    a0 = band.n - 2
    v = int(nbr[a0][nbr[a0] >= 0][24])              # plane 2, interior
    part = bpart.copy()
    part[v] = 2
    lock = np.ones(band.n, bool)
    lock[v] = False                                  # only v can move
    vwgt = np.asarray(band.vwgt).copy()
    vwgt[a0] = 300                                   # side 0 infeasible
    w = FMWork(nbr=nbr, vwgt=vwgt, part=part, locked=lock, seed=0, k_inst=2)
    assert list(w.split.rows) == [a0, a0 + 1]
    outs = []
    for full_width in (None, 64):
        (nbr_l, vw_l, parts_l, lock_l, *_), (sid, scnt) = _stack(
            w, full_width)
        if full_width:
            sid, scnt = _no_side(*vw_l.shape)
        vw_f = vw_l.astype(jnp.float32)
        p0, p1 = _pulled_jnp(jnp.where(nbr_l >= 0, nbr_l, 0), nbr_l >= 0,
                             vw_f, parts_l)
        pt = parts_l[0].astype(jnp.int32)
        sums = [jnp.sum(vw_f[0] * (pt == s)) for s in (0, 1, 2)]
        outs.append(fm_move_loop(
            jnp.where(nbr_l[0] >= 0, nbr_l[0], 0), nbr_l[0] >= 0, vw_f[0],
            lock_l[0], sid[0], scnt[0], jnp.float32(0), pt, p0[0], p1[0],
            *sums, pt, sums[2], jnp.abs(sums[0] - sums[1]),
            jnp.zeros((2, pt.shape[0])), jnp.int32(0), jnp.int32(1)))
        before = np.stack([np.asarray(p0[0]), np.asarray(p1[0])])
    split, whole = outs
    part_after = np.asarray(split[0])
    assert part_after[v] == 1 and part_after[a0] == 2   # anchor pulled
    for name, x, y in zip(("part", "w0", "w1", "ws"), split, whole):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name
    keep = np.ones(part_after.shape[0], bool)
    keep[[a0, a0 + 1]] = False
    pulled_s, pulled_w = np.asarray(split[7]), np.asarray(whole[7])
    assert np.array_equal(pulled_s[:, keep], pulled_w[:, keep])
    # the anchor's row reached its far neighbours, not v's alone
    far = np.setdiff1d(nbr[a0][nbr[a0] >= 0], nbr[v])
    assert (pulled_w[1, far] != before[1, far]).all()


def _rows(widths, locked, packed, d=64, n=100):
    """A work whose row ``i`` holds ``widths[i]`` neighbours (8 else),
    in its first slots where ``packed``, else in slots drawn at random."""
    rng = np.random.default_rng(len(widths))
    nbr = -np.ones((n, d), np.int32)
    deg = np.full(n, 8)
    deg[:len(widths)] = widths
    for r in range(n):
        cols = np.arange(deg[r]) if packed else rng.permutation(d)[:deg[r]]
        nbr[r, cols] = rng.integers(0, n, deg[r])
    lock = np.zeros(n, bool)
    lock[list(locked)] = True
    return FMWork(nbr=nbr, vwgt=np.ones(n, np.int64),
                  part=np.zeros(n, np.int8), locked=lock, seed=0)


@pytest.mark.parametrize("widths,locked,side,d_pad", [
    ((40, 50), (0, 1), [1, 0], 8),              # two anchors leave
    ((40, 50, 6), (0, 1, 2), [1, 0], 8),        # the two widest locked
    ((40, 8), (0, 1), [0], 8),                  # one wide, one narrow
    ((40, 40, 40), (0, 1, 2), [], 64),          # three wide: whole table
    ((40, 40, 60), (0, 1), [], 64),             # an unlocked row is wider
    ((40, 30, 20), (0, 1), [0], 32),            # the rest is 30 wide
    ((), (), [], 64)])                          # no locks: whole table
@pytest.mark.parametrize("packed", [True, False])
def test_the_split_reads_locks_and_degrees(widths, locked, side, d_pad,
                                           packed):
    w = _rows(widths, locked, packed)
    assert list(w.split.rows) == side and len(side) <= SIDE_K
    assert w.bucket_key()[1] == d_pad
    deg = np.count_nonzero(w.nbr >= 0, axis=1)
    assert w.split.slots == int(deg.sum() - deg[side].sum())
    ln = _prepare_lanes(w)
    assert ln.nbr.shape[2] == d_pad
    assert (ln.nbr[0, side] < 0).all()
    assert np.count_nonzero(ln.nbr[0] >= 0) == w.split.slots
    stay = np.setdiff1d(np.arange(w.nbr.shape[0]), side)
    for r in stay:                      # each row keeps its neighbours
        assert sorted(ln.nbr[0, r][ln.nbr[0, r] >= 0]) == \
            sorted(w.nbr[r][w.nbr[r] >= 0])
    for h, r in enumerate(side):
        assert ln.side_id[0, h] == r
        assert np.array_equal(ln.side_cnt[0, h, :w.nbr.shape[0]],
                              np.bincount(w.nbr[r][w.nbr[r] >= 0],
                                          minlength=w.nbr.shape[0]))
    assert (ln.side_id[0, len(side):] == ln.nbr.shape[1]).all()
    assert not ln.side_cnt[0, len(side):].any()
