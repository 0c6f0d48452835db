"""The FM move loop's counters: computed on the device by every path,
carried on the ``launch`` event, and free of new executables.

* hoisted, fused (interpret) and oracle FM return the same counters on
  the parity suite's lane stacks, beside the same partitions;
* a lane with no move budget counts no iteration;
* a band graph's anchors leave its table, ``slots`` counts the real
  neighbours of the rows that stay, and ``side_lanes`` the lanes whose
  anchors left;
* ``execute_fm_works`` puts ``trips``, ``lane_iters``,
  ``iters_after_best``, ``slots``, ``side_lanes`` and, but for the
  oracle, ``pull_overflow`` on its ``launch``;
* tracing on or off dispatches the same executables;
* the host steps of nested dissection emit their ``stage`` events.
"""
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from repro import obs  # noqa: E402
from repro.core.band import extract_band  # noqa: E402
from repro.core.fm import (FMWork, execute_fm_works,  # noqa: E402
                           fm_refine_multi)
from repro.graphs import generators as G  # noqa: E402
from repro.kernels.fm_fused import fm_fused_multi  # noqa: E402
from test_fm_fused import (_assert_bit_identical, _rand_lanes,  # noqa: E402
                           _run_all_three)

LAUNCH_KEYS = ("trips", "lane_iters", "iters_after_best", "slots",
               "side_lanes")
DEVICE_KEYS = LAUNCH_KEYS + ("pull_overflow",)     # not the oracle's


def _assert_same_counts(a, b, what):
    """The counters both sides report: the oracle has ``iters`` and
    ``last_better``, hoisted and fused ``pull_overflow`` besides."""
    x, y = np.asarray(a[3]), np.asarray(b[3])
    k = min(x.shape[-1], y.shape[-1])
    assert np.array_equal(x[..., :k], y[..., :k]), \
        f"{what}: moves differ\n{x}\n{y}"


class _Events:
    def __init__(self):
        self.events = []

    def on_event(self, kind, payload):
        self.events.append((kind, dict(payload)))

    def of(self, kind):
        return [p for k, p in self.events if k == kind]


def _collect(fn):
    ev = _Events()
    obs.register_collector(ev)
    try:
        out = fn()
    finally:
        obs.unregister_collector(ev)
    return out, ev


@pytest.mark.parametrize("L,passes,pos_only",
                         [(1, 3, False), (3, 3, False), (8, 3, False),
                          (3, 1, True), (3, 3, True)])
def test_three_paths_count_the_same_moves(L, passes, pos_only):
    args = _rand_lanes(seed=10 + L, L=L, n=32, d=4)
    hoisted, fused, oracle = _run_all_three(args, passes=passes,
                                            pos_only=pos_only)
    _assert_bit_identical(fused, hoisted, "fused vs hoisted")
    _assert_bit_identical(fused, oracle, "fused vs oracle")
    _assert_same_counts(fused, hoisted, "fused vs hoisted")
    _assert_same_counts(fused, oracle, "fused vs oracle")
    moves = np.asarray(hoisted[3])
    assert moves.shape == (L, passes, 3) and moves.dtype == np.int32
    iters, last, overflow = moves[..., 0], moves[..., 1], moves[..., 2]
    mm = np.asarray(args[6])[:, None]
    assert (iters <= mm).all() and (0 <= last).all() and (last <= iters).all()
    assert iters.sum() > 0 and (iters > last).any()
    assert (overflow == 0).all()            # d = 4: the whole row is a list


def test_a_lane_without_moves_counts_no_iteration():
    nbr, vwgt, parts0, locked, keys, eps, mm, n_pert = _rand_lanes(
        seed=21, L=3, n=32, d=4)
    mm = mm.at[1].set(0)
    args = (nbr, vwgt, parts0, locked, keys, eps, mm, n_pert)
    for out in _run_all_three(args, passes=3, pos_only=False):
        moves = np.asarray(out[3])
        assert (moves[1] == 0).all(), moves[1]
        assert (moves[0, :, 0] > 0).any() and (moves[2, :, 0] > 0).any()
        # the lane's state is its start: no move, no change
        assert np.array_equal(np.asarray(out[0][1]), np.asarray(parts0[1]))


def _plane_band(k=7, width=1):
    """The band around the plane x == k // 2 of a k^3 27-point grid."""
    g = G.grid3d(k, k, k, stencil=27)
    x = np.arange(g.n) // (k * k)
    part = np.where(x < k // 2, 0, np.where(x > k // 2, 1, 2)).astype(np.int8)
    return extract_band(g, part, width=width)


def test_anchor_leaves_the_table():
    band, bpart, locked, _ = _plane_band()
    deg = band.degrees()
    assert int(deg[-2:].min()) == 49 > 32 > int(deg[:-2].max()) == 26
    nbr, _ = band.to_ell()
    assert nbr.shape[1] == 49                       # one 7x7 plane
    w = FMWork(nbr=nbr, vwgt=band.vwgt, part=bpart, locked=locked,
               seed=3, k_inst=4)
    _, ev = _collect(lambda: execute_fm_works([w], mode="hoisted"))
    (launch,) = ev.of("launch")
    assert launch["slots"] == 4 * int(deg[:-2].sum())
    assert launch["side_lanes"] == 4
    n_pad, d_pad = launch["bucket"][:2]
    assert d_pad == 32 and launch["slots"] < 4 * n_pad * d_pad


@pytest.mark.parametrize("mode", ["hoisted", "fused", "oracle"])
def test_launch_carries_the_move_counters(mode):
    band, bpart, locked, _ = _plane_band()
    nbr, _ = band.to_ell()
    works = [FMWork(nbr=nbr, vwgt=band.vwgt, part=bpart, locked=locked,
                    seed=s, k_inst=k) for s, k in ((1, 2), (2, 3))]
    res, ev = _collect(lambda: execute_fm_works(works, mode=mode))
    (launch,) = ev.of("launch")
    assert launch["kind"] == "fm"
    assert all(isinstance(launch[k], int) for k in LAUNCH_KEYS)
    lanes, lanes_pad = launch["lanes"], launch["lanes_pad"]
    assert (lanes, lanes_pad) == (2 + 4, 8)       # k_inst rounds up to 4
    passes = launch["bucket"][2]
    budget = works[0].effective_max_moves()
    assert 0 < launch["trips"] <= passes * budget
    assert launch["trips"] <= launch["lane_iters"] \
        <= lanes * launch["trips"]
    assert 0 <= launch["iters_after_best"] <= launch["lane_iters"]
    # the bucket's counts, whichever rung ran: the oracle's whole table too
    assert launch["slots"] == lanes * int(band.degrees()[:-2].sum())
    assert launch["side_lanes"] == lanes
    if mode == "oracle":
        assert "pull_overflow" not in launch
    else:
        assert 0 <= launch["pull_overflow"] <= launch["lane_iters"]
    # the counters read what the program ran: the same on every path
    ref, ev_ref = _collect(lambda: execute_fm_works(works, mode="hoisted"))
    (ref_launch,) = ev_ref.of("launch")
    keys = LAUNCH_KEYS if mode == "oracle" else DEVICE_KEYS
    assert {k: launch[k] for k in keys} == {k: ref_launch[k] for k in keys}
    for (p, s, i), (q, t, j) in zip(res, ref):
        assert np.array_equal(p, q) and s == t and i == j


@pytest.mark.parametrize("mode,program",
                         [("hoisted", fm_refine_multi),
                          ("fused", fm_fused_multi)])
def test_tracing_uses_the_same_executables(mode, program):
    band, bpart, locked, _ = _plane_band(k=6)
    nbr, _ = band.to_ell()
    works = [FMWork(nbr=nbr, vwgt=band.vwgt, part=bpart, locked=locked,
                    seed=5, k_inst=2)]
    plain, _ = _collect(lambda: execute_fm_works(works, mode=mode))
    size = program._cache_size()

    def traced():
        with obs.tracing() as tr:
            out = execute_fm_works(works, mode=mode)
        return out, tr

    (out, tr), ev = _collect(traced)
    assert program._cache_size() == size
    assert [p["compile"] for p in ev.of("stage")] == [False]
    assert [s.attrs["compile"] for s in tr.spans
            if s.name == "dispatch:fm"] == [False]
    assert all(k in ev.of("launch")[0] for k in DEVICE_KEYS)
    for (p, s, i), (q, t, j) in zip(plain, out):
        assert np.array_equal(p, q) and s == t and i == j


def test_host_nd_steps_emit_their_stages():
    from repro.service.scheduler import order_batch
    graphs = [G.grid2d(14, 13)]

    def traced():
        with obs.tracing() as tr:
            perms = order_batch(graphs, seeds=[0])
        return perms, tr

    (perms, tr), ev = _collect(traced)
    for g, p in zip(graphs, perms):
        assert np.array_equal(np.sort(p), np.arange(g.n))
    stages = {p["name"] for p in ev.of("stage")}
    assert {"band", "split", "leaf_order", "sep_order"} <= stages
    assert all(not p["compile"] for p in ev.of("stage")
               if p["name"] in ("band", "split", "leaf_order", "sep_order"))
    names = {s.name for s in tr.spans}
    assert {"stage:band", "stage:split", "stage:leaf_order",
            "stage:sep_order"} <= names
    assert np.array_equal(perms[0], order_batch(graphs, seeds=[0])[0])
