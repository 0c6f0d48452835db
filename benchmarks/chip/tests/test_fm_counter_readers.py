"""The readers of the FM move-loop counters and the host ND stages, on
made-up windows: known values, and nothing where there is nothing to
read (an empty window, or launches without the counters)."""
import os
import sys
import types

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

import spec  # noqa: E402

READERS = ("fm.trips_per_ordering", "fm.lane_iter_fill",
           "fm.iters_after_best_share", "fm.ell_fill",
           "fm.device_us_per_trip", "host.nd_stage_share")


def _view(**kw):
    base = dict(seconds=10.0, t_open=0.0, t_close=10.0, requests=[],
                completed=[], events=[], spans=None, trace=None,
                trace_events=[], peaks={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _read(name, view):
    return spec.load_reader(CHIP, name)(view)


def _fm(lanes, pad, n_pad, d_pad, **counts):
    return (1.0, "launch", {"kind": "fm", "lanes": lanes, "lanes_pad": pad,
                            "bucket": (n_pad, d_pad, 3, False), **counts})


def _stage(name, s, c=False):
    return (1.0, "stage", {"name": name, "seconds": s, "compile": c})


A = dict(trips=100, lane_iters=600, iters_after_best=300, slots=4096)
B = dict(trips=50, lane_iters=300, iters_after_best=60, slots=1024)


def _window():
    other = (1.0, "launch", {"kind": "bfs", "lanes": 3, "lanes_pad": 4,
                             "bucket": (64, 8), "rounds": 3})
    ev = [_fm(6, 8, 128, 64, **A), _fm(8, 8, 64, 32, **B), other,
          _stage("fm", 2.0), _stage("band", 0.5), _stage("split", 0.25),
          _stage("leaf_order", 0.75), _stage("sep_order", 0.5),
          _stage("endgame", 3.0), _stage("fm", 1.0, True)]
    done = [types.SimpleNamespace(n=10)] * 3
    return _view(events=ev, completed=done)


def test_readers_on_a_made_up_window():
    v = _window()
    assert _read("fm.trips_per_ordering", v) == pytest.approx(150 / 3)
    assert _read("fm.lane_iter_fill", v) == pytest.approx(
        100 * 900 / (8 * 100 + 8 * 50))
    assert _read("fm.iters_after_best_share", v) == pytest.approx(
        100 * 360 / 900)
    assert _read("fm.ell_fill", v) == pytest.approx(
        100 * (4096 + 1024) / (6 * 128 * 64 + 8 * 64 * 32))
    assert _read("host.nd_stage_share", v) == pytest.approx(20.0)
    # the device reader reads the traced tail, not the window
    assert _read("fm.device_us_per_trip", v) is None
    v.trace = {"modules": {"jit_fm_refine_multi(7)": 0.03,
                           "jit_bfs_multi(2)": 1.0},
               "busy_s": 1.0, "window_s": 2.0}
    assert _read("fm.device_us_per_trip", v) is None      # no tail launch
    v.trace_events = [_fm(6, 8, 128, 64, **A), _fm(6, 8, 128, 64, **B)]
    assert _read("fm.device_us_per_trip", v) == pytest.approx(
        1e6 * 0.03 / 150)


def test_readers_find_nothing_in_an_empty_window():
    v = _view(trace={"modules": {}, "busy_s": 0.0, "window_s": 1.0})
    for name in READERS:
        assert _read(name, v) is None, name


def test_readers_find_nothing_without_the_counters():
    """A program whose ``launch`` events lack the counters (one older
    than them) reads nothing, and no reader raises."""
    bare = [_fm(6, 8, 128, 64), _fm(8, 8, 64, 32), _stage("fm", 2.0),
            _stage("endgame", 3.0)]
    v = _view(events=bare, completed=[types.SimpleNamespace(n=10)],
              trace={"modules": {"jit_fm_refine_multi(7)": 0.03},
                     "busy_s": 1.0, "window_s": 2.0},
              trace_events=bare[:1])
    for name in READERS:
        assert _read(name, v) is None, name


def test_readers_find_nothing_where_no_move_ran():
    zero = dict(trips=0, lane_iters=0, iters_after_best=0, slots=0)
    v = _view(events=[_fm(2, 8, 64, 8, **zero)],
              trace={"modules": {"jit_fm_refine_multi(7)": 0.03},
                     "busy_s": 1.0, "window_s": 2.0},
              trace_events=[_fm(2, 8, 64, 8, **zero)])
    assert _read("fm.trips_per_ordering", v) is None      # none completed
    assert _read("fm.lane_iter_fill", v) is None
    assert _read("fm.iters_after_best_share", v) is None
    assert _read("fm.device_us_per_trip", v) is None
    assert _read("fm.ell_fill", v) == 0.0
