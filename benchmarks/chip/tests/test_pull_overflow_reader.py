"""The reader of ``fm.pull_overflow_share`` on made-up windows: a known
value, and nothing where there is nothing to read (an empty window, no
move run, or a program whose ``launch`` events lack ``pull_overflow``)."""
import os
import sys
import types

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

import spec  # noqa: E402

NAME = "fm.pull_overflow_share"


def _read(events):
    run = types.SimpleNamespace(events=events)
    return spec.load_reader(CHIP, NAME)(run)


def _fm(**counts):
    return (1.0, "launch", {"kind": "fm", "lanes": 6, "lanes_pad": 8,
                            "bucket": (128, 64, 3, False), **counts})


A = dict(trips=100, lane_iters=600, iters_after_best=300, slots=4096,
         pull_overflow=30)
B = dict(trips=50, lane_iters=300, iters_after_best=60, slots=1024,
         pull_overflow=6)
OTHER = (1.0, "launch", {"kind": "bfs", "lanes": 3, "lanes_pad": 4,
                         "bucket": (64, 8), "rounds": 3})


def test_reads_overflowing_moves_over_moves_run():
    assert _read([_fm(**A), OTHER, _fm(**B)]) == pytest.approx(
        100 * 36 / 900)


@pytest.mark.parametrize("events", [
    [],                                                 # empty window
    [OTHER],                                            # no FM launch
    [_fm(**{**A, "lane_iters": 0, "pull_overflow": 0})],    # no move ran
    [_fm(trips=1)],                                     # no counters
    [_fm(**{k: v for k, v in A.items() if k != "pull_overflow"}),
     _fm(**B)],                          # a program older than the counter
], ids=["empty", "no_fm", "no_move", "bare", "before_the_counter"])
def test_reads_nothing_where_there_is_nothing_to_read(events):
    assert _read(events) is None
