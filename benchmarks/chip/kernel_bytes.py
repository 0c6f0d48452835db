"""Least work of the device programs, from their shapes, and the chip's
peaks (``peaks.json``, keyed by the ``device_kind`` JAX reports).

The FM refinement (``fm_refine_multi`` on the chip's XLA path) moves
separator vertices one at a time and does no matrix work: its arithmetic
is integer compare, select and add, far below the chip's vector rate.
What bounds any implementation of it is memory: it has to read each
lane's neighbour table and vertex state at least once and write each
lane's result once.  ``fm_least_bytes`` counts exactly that, whatever
implements the passes, so the roofline share it gives is a share of the
memory bound (``FM_BOUND``) and cannot pass 100% unless the device time
leaves out part of the work.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
FM_BOUND = "hbm_bytes_per_s"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def fm_least_bytes(lanes: int, n_pad: int, d_pad: int) -> int:
    """Bytes one FM dispatch has to move at the least.

    Per lane, read once: the int32 neighbour table (n_pad x d_pad), int32
    vertex weights, int8 start parts, bool locks, a 2 x uint32 key and
    three 4-byte scalars (balance tolerance, move budget, perturbation);
    written once: int8 parts and two float32 scalars.  The number of
    passes does not change the least: a pass loop that keeps the table
    on chip reads it once for all passes.
    """
    read = n_pad * d_pad * 4 + n_pad * (4 + 1 + 1) + 2 * 4 + 3 * 4
    write = n_pad + 2 * 4
    return lanes * (read + write)
