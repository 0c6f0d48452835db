"""The reader of ``fm.side_lane_share`` on made-up windows: a known
value, and nothing where there is nothing to read (an empty window, no
FM launch, or a program whose ``launch`` events lack ``side_lanes``)."""
import os
import sys
import types

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

import spec  # noqa: E402

NAME = "fm.side_lane_share"


def _read(events):
    run = types.SimpleNamespace(events=events)
    return spec.load_reader(CHIP, NAME)(run)


def _fm(lanes, **counts):
    return (1.0, "launch", {"kind": "fm", "lanes": lanes, "lanes_pad": 8,
                            "bucket": (2048, 32, 3, False), **counts})


A = dict(trips=100, lane_iters=600, iters_after_best=300, slots=4096,
         pull_overflow=30, side_lanes=4)
B = dict(trips=50, lane_iters=300, iters_after_best=60, slots=1024,
         pull_overflow=6, side_lanes=0)
OTHER = (1.0, "launch", {"kind": "bfs", "lanes": 3, "lanes_pad": 4,
                         "bucket": (64, 8), "rounds": 3})


def test_reads_lanes_with_side_rows_over_lanes():
    assert _read([_fm(6, **A), OTHER, _fm(4, **B)]) == pytest.approx(
        100 * 4 / 10)


@pytest.mark.parametrize("events", [
    [],                                                 # empty window
    [OTHER],                                            # no FM launch
    [_fm(0, **A)],                                      # no real lane
    [_fm(6, trips=1)],                                  # no counters
    [_fm(6, **{k: v for k, v in A.items() if k != "side_lanes"}),
     _fm(4, **B)],                      # a program older than the counter
], ids=["empty", "no_fm", "no_lane", "bare", "before_the_counter"])
def test_reads_nothing_where_there_is_nothing_to_read(events):
    assert _read(events) is None
