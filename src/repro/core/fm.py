"""Vertex-separator FM refinement, multi-sequential (paper §3.3), in JAX.

State per vertex: part ∈ {0, 1, 2=separator, 3=padding}.  Invariant: no edge
joins part 0 to part 1.  A move takes a separator vertex v to side p; every
neighbor of v in side 1−p is pulled into the separator (preserving the
invariant).  Gain = vwgt[v] − Σ pulled weights.  Moves may be negative
(hill-climbing); the best state seen is restored at end of pass.

The paper's *multi-sequential* refinement — "centralized copies of this band
graph ... serve to run fully independent instances of our sequential FM
algorithm; the perturbation of the initial state ... allows us to explore
slightly different solution spaces" — is a ``vmap`` over independent
instances.  Since the service PR, the batch axis is a flat *lane* axis that
may mix instances of *different* graphs padded to the same ELL bucket: the
ordering service gathers band-FM work from every ND node at the same depth
and executes one batched dispatch per shape bucket (DESIGN.md §3) — by
default the fused on-device pass loop (``kernels.fm_fused``), with this
module's ``fm_refine_multi`` as the bit-identical hoisted reference path
(``REPRO_FM_MODE``).  Per-lane results are independent of batch
composition, so bucketed execution is bit-compatible with
one-work-at-a-time execution.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from collections import defaultdict
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fm_fused import SIDE_K, fm_move_loop as _fm_pass
from repro.util import pow2 as _pow2    # shared bucketing: one definition

NEG_INF = -jnp.inf
BIG_NOISE = 1e9


# --------------------------------------------------------------------- #
# device data plane
# --------------------------------------------------------------------- #
# The per-lane move loop (``_fm_pass``) lives in ``kernels.fm_fused``:
# it is shared verbatim between this hoisted path (vmapped below) and
# the fused on-device pass loop, so the two cannot drift.


def _pulled_jnp(nbrs, valid, vwgt_f, part):
    """pulled_to{0,1}[l, v] = weight of N(v) in side {1, 0} (O(L·n·d))."""
    L, n, d = nbrs.shape
    flat = nbrs.reshape(L, n * d)
    pn = jnp.take_along_axis(part, flat, axis=1).reshape(L, n, d)
    wn = jnp.take_along_axis(vwgt_f, flat, axis=1).reshape(L, n, d)
    wn = jnp.where(valid, wn, 0.0)
    return (jnp.sum(wn * (pn == 1), axis=2),
            jnp.sum(wn * (pn == 0), axis=2))


def _pulled_all(nbrs, valid, vwgt_f, part, gain_mode: str):
    """Per-pass gain recompute over all lanes of a bucket.

    ``pallas`` routes through the batched Mosaic gain kernel
    (``repro.kernels.band_batch.sep_gain_multi``); ``jnp`` is the fused-XLA
    reference (identical reduction order, so results are bit-equal).
    """
    if gain_mode == "pallas":
        from repro.kernels.ops import sep_gain_batch
        return sep_gain_batch(jnp.where(valid, nbrs, -1), vwgt_f,
                              part.astype(jnp.int32))
    return _pulled_jnp(nbrs, valid, vwgt_f, part)


def fm_lane_count(nproc: int, cap: int, fold_dup: bool,
                  strict: bool = False) -> int:
    """Multi-sequential FM lane count for a process group of ``nproc``.

    The paper runs one independent sequential FM instance per process of
    the group refining a band (§3.3); ``cap`` bounds the lane memory,
    ``fold_dup=False`` (ablation) keeps the host floor of two lanes, and
    ``strict`` (the ParMETIS-like baseline) runs a single lane.  Shared by
    the sequential pipeline and the distributed band refinement so both
    derive identical lane counts.
    """
    if strict:
        return 1
    k = int(np.clip(nproc, 1, cap)) if fold_dup else 1
    return max(k, 2)


def gain_mode_default() -> str:
    """FM gain-recompute backend: REPRO_FM_GAIN=jnp|pallas|auto.

    ``auto`` is the fused-XLA path (``jnp``) on every platform: on TPU
    the v5e compiler refuses the ``sep_gain_multi`` kernel (its ``(1,
    n)`` blocks break the (8, 128) block rule), and on CPU hosts Pallas
    would only run in interpret mode.
    """
    mode = os.environ.get("REPRO_FM_GAIN", "auto")
    if mode == "auto":
        return "jnp"
    return mode


@functools.partial(jax.jit, static_argnames=("passes", "pos_only",
                                             "gain_mode"))
def fm_refine_multi(nbr, vwgt, parts_init, locked, keys, eps_frac,
                    max_moves, n_pert, side_id, side_cnt, passes: int = 3,
                    pos_only: bool = False, gain_mode: str = "jnp"):
    """FM over a flat lane axis: any mix of (graph, instance) pairs.

    Shapes (L = lanes): nbr (L, n, d) int32; vwgt (L, n); parts_init
    (L, n) int8; locked (L, n) bool; keys (L, 2) uint32; eps_frac (L,)
    f32; max_moves, n_pert (L,) int32; side_id (L, SIDE_K) int32 and
    side_cnt (L, SIDE_K, n) f32, the locked rows left out of ``nbr``
    (``kernels.fm_fused``; ``n`` and zeros for none).  Returns (parts,
    sep_w, imb, moves) with leading lane axis; ``moves`` (L, passes, 3) int32 holds
    each pass's move counters (``fm_move_loop``).  The pass loop is
    hoisted out of the per-lane body so the O(L·n·d) gain recompute runs
    as ONE batched kernel per pass.

    This is the *hoisted* reference path (``REPRO_FM_MODE=hoisted``);
    the default production path is the fused on-device pass loop
    (``kernels.fm_fused.fm_fused_multi``), bit-identical to this one —
    the differential parity suite (``tests/test_fm_fused.py``) holds
    both against the independent jnp oracle in ``kernels.ref``.
    """
    L, n, d = nbr.shape
    valid = nbr >= 0
    nbrs = jnp.where(valid, nbr, 0)
    vwgt_f = vwgt.astype(jnp.float32)
    total = vwgt_f.sum(axis=1)
    eps_abs = eps_frac.astype(jnp.float32) * total

    def sums(part):
        w0 = jnp.sum(vwgt_f * (part == 0), axis=1)
        w1 = jnp.sum(vwgt_f * (part == 1), axis=1)
        ws = jnp.sum(vwgt_f * (part == 2), axis=1)
        return w0, w1, ws

    part = parts_init
    w0, w1, ws = sums(part)
    bpart, bws, bimb = part, ws, jnp.abs(w0 - w1)
    pert = n_pert                       # perturbation active in pass 1 only
    pass_fn = functools.partial(_fm_pass, pos_only=pos_only)
    moves = []
    for p in range(passes):
        both = jax.vmap(jax.random.split)(keys)             # (L, 2, 2)
        keys, subs = both[:, 0], both[:, 1]
        # per-pass tiebreak noise (moved-locks make per-move noise redundant)
        noise = jax.vmap(lambda k: jax.random.uniform(k, (2, n)))(subs)
        pulled0, pulled1 = _pulled_all(nbrs, valid, vwgt_f, part, gain_mode)
        (part, w0, w1, ws, bpart, bws, bimb, _, *counts) = jax.vmap(
            pass_fn)(nbrs, valid, vwgt_f, locked, side_id, side_cnt, eps_abs,
                     part, pulled0, pulled1, w0, w1, ws, bpart, bws, bimb,
                     noise, pert, max_moves)
        moves.append(jnp.stack(counts, axis=1))             # (L, 3)
        part = bpart                                        # revert to best
        w0, w1, ws = sums(part)
        pert = jnp.zeros_like(pert)
    return bpart, bws, bimb, jnp.stack(moves, axis=1)


# --------------------------------------------------------------------- #
# host work descriptors + bucketed executor
# --------------------------------------------------------------------- #
class _Split(NamedTuple):
    """A work's side rows, and the table that stays."""
    rows: np.ndarray                    # the side rows' ids, widest first
    width: int                          # d_pad of the rows that stay
    slots: int                          # their real neighbour slots


@dataclasses.dataclass
class FMWork:
    """One multi-instance FM refinement request (unpadded host arrays).

    The pipeline stages in ``core.nd`` *yield* these instead of dispatching
    directly; ``execute_fm_works`` pads each to its power-of-two ELL bucket
    and runs every work sharing a bucket in a single ``fm_refine_multi``
    dispatch (one lane per FM instance).

    ``locked`` and ``max_moves`` are *lane data*, not part of
    ``bucket_key``: works whose locked masks or move budgets differ
    (e.g. the per-phase boundary-color masks of the sharded-band
    alternating schedule, ``dnd._sharded_band_task``) still batch into
    one dispatch, because every lane's mask and budget ride in as input
    arrays of the kernel — only fields that change the compiled program
    (padded n / d, passes, pos_only) key the bucket.  A locked vertex
    cannot be *selected* for a move, but a move may still *pull* it into
    the separator; schedulers that lock remote-owned copies must
    propagate such pulls themselves.

    Side rows: the at most ``SIDE_K`` widest locked rows that are each
    wider than ``pow2(max degree of the rows that stay, 8)`` (a band's
    anchors, wired to a whole band layer) leave the padded table, so
    ``bucket_key``'s ``d_pad`` is the width of the rows that stay; the
    device carries them as dense count vectors (``kernels.fm_fused``).
    The split is read from the locks and degrees alone.
    """
    nbr: np.ndarray                     # (n, d) int32 ELL ids, -1 pad
    vwgt: np.ndarray                    # (n,) vertex weights
    part: np.ndarray                    # (n,) int8 initial state
    locked: np.ndarray                  # (n,) bool
    seed: int
    k_inst: int = 8
    eps_frac: float = 0.1
    passes: int = 3
    max_moves: Optional[int] = None
    n_pert: int = 8
    parts_init: Optional[np.ndarray] = None    # (K, n) distinct starts
    pos_only: bool = False

    def effective_max_moves(self) -> int:
        n_pad = _pow2(self.nbr.shape[0])
        max_moves = self.max_moves
        if max_moves is None:
            if self.parts_init is None:
                sep_sz = int((self.part == 2).sum())
            else:
                sep_sz = int((np.asarray(self.parts_init) == 2).sum(1).max())
            max_moves = 2 * sep_sz + 16
        return min(int(max_moves), n_pad, 4096)

    @functools.cached_property
    def split(self) -> _Split:
        deg = np.count_nonzero(self.nbr >= 0, axis=1)
        locked = np.flatnonzero(self.locked)
        wide = locked[np.argsort(-deg[locked], kind="stable")[:SIDE_K]]
        for k in range(len(wide), 0, -1):
            rest = deg.copy()
            rest[wide[:k]] = -1
            width = _pow2(max(int(rest.max(initial=0)), 1), 8)
            if deg[wide[k - 1]] > width:        # the k widest all leave
                return _Split(wide[:k], width,
                              int(deg.sum() - deg[wide[:k]].sum()))
        return _Split(wide[:0], _pow2(max(self.nbr.shape[1], 1), 8),
                      int(deg.sum()))

    def bucket_key(self) -> Tuple[int, int, int, bool]:
        # max_moves is adaptive per lane, NOT sub-bucketed: the fused
        # kernel's grid runs one lane at a time, so each lane's move
        # loop terminates at its own budget — mixing small budgets with
        # large ones serializes nothing.  (The hoisted path's vmapped
        # while_loop select-masks finished lanes, so per-lane results
        # are budget-composition-independent there too.)  Fewer buckets
        # ⇒ fewer compiles and wider lane stacks per dispatch.
        return (_pow2(self.nbr.shape[0]), self.split.width, self.passes,
                self.pos_only)


@dataclasses.dataclass
class _Lanes:
    """One work's padded per-lane arrays (k_inst lanes)."""
    nbr: np.ndarray                     # (k, n_pad, d_pad) — broadcast view
    vwgt: np.ndarray
    locked: np.ndarray
    parts0: np.ndarray
    keys: np.ndarray
    eps: np.ndarray
    max_moves: np.ndarray
    n_pert: np.ndarray
    side_id: np.ndarray                 # (k, SIDE_K) — broadcast view
    side_cnt: np.ndarray                # (k, SIDE_K, n_pad) — broadcast view


def _prepare_lanes(w: FMWork, full_width: Optional[int] = None) -> _Lanes:
    """Pad ``w`` to its bucket, its side rows left out of the table; with
    ``full_width`` the table keeps them, padded to that width (the
    oracle rung, which has no side rows)."""
    n = w.nbr.shape[0]
    n_pad, d_pad = w.bucket_key()[:2]
    k_inst = _pow2(w.k_inst, 2)
    side = w.split.rows if full_width is None else ()
    side_id = np.full(SIDE_K, n_pad, np.int32)
    side_id[:len(side)] = side
    side_cnt = np.zeros((SIDE_K, n_pad), np.float32)
    nbr = w.nbr
    if len(side):
        nbr = nbr.copy()
        for h, r in enumerate(side):
            row = nbr[r][nbr[r] >= 0]
            side_cnt[h] = np.bincount(row, minlength=n_pad)
        nbr[side] = -1
        if (nbr[:, d_pad:] >= 0).any():          # left-pack the rows
            nbr = np.take_along_axis(
                nbr, np.argsort(nbr < 0, axis=1, kind="stable"), axis=1)
        nbr = nbr[:, :d_pad]
    else:
        d_pad = full_width or d_pad
    nbr_p = -np.ones((n_pad, d_pad), np.int32)
    nbr_p[:n, :nbr.shape[1]] = nbr
    vw_p = np.zeros(n_pad, np.int32)
    vw_p[:n] = w.vwgt
    lock_p = np.ones(n_pad, bool)
    lock_p[:n] = w.locked
    if w.parts_init is None:
        parts_init = np.broadcast_to(np.asarray(w.part, np.int8)[None, :],
                                     (k_inst, n))
    else:
        parts_init = np.asarray(w.parts_init, np.int8)[
            np.arange(k_inst) % len(w.parts_init)]
    max_moves = w.effective_max_moves()
    parts0 = np.full((k_inst, n_pad), 3, np.int8)
    parts0[:, :n] = parts_init
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(w.seed), k_inst))
    return _Lanes(
        nbr=np.broadcast_to(nbr_p, (k_inst, n_pad, d_pad)),
        vwgt=np.broadcast_to(vw_p, (k_inst, n_pad)),
        locked=np.broadcast_to(lock_p, (k_inst, n_pad)),
        parts0=parts0, keys=keys,
        eps=np.full(k_inst, w.eps_frac, np.float32),
        max_moves=np.full(k_inst, max_moves, np.int32),
        n_pert=np.full(k_inst, w.n_pert, np.int32),
        side_id=np.broadcast_to(side_id, (k_inst, SIDE_K)),
        side_cnt=np.broadcast_to(side_cnt, (k_inst, SIDE_K, n_pad)))


def _select_best(w: FMWork, parts: np.ndarray, sep_w: np.ndarray,
                 imb: np.ndarray) -> Tuple[np.ndarray, float, float]:
    """Paper's selection: min separator weight among balance-feasible."""
    total = float(np.asarray(w.vwgt).sum())
    feas = imb <= max(w.eps_frac * total, float(imb.min()))
    score = np.where(feas, sep_w, sep_w + total)            # infeasible last
    best = int(np.argmin(score))
    return parts[best], float(sep_w[best]), float(imb[best])


def _launch_counts(moves: np.ndarray, lanes: int) -> dict:
    """The ``launch`` payload's move-loop counters of one FM dispatch.

    ``trips`` sums, over passes, the most moves any lane ran: the serial
    iterations of the vmapped move loop, which runs until its slowest
    lane stops (dummy lanes run none).  The rest are over the ``lanes``
    real lanes: moves run (``lane_iters``), moves after each pass's
    last improvement, which the revert to best throws away
    (``iters_after_best``), and moves whose pulled set took more than
    one scatter round (``pull_overflow``; the oracle does not count it).
    """
    moves = np.asarray(moves, np.int64)             # (L_pad, passes, 2|3)
    iters, last = moves[:lanes, :, 0], moves[:lanes, :, 1]
    counts = {"trips": int(moves[:, :, 0].max(axis=0).sum()),
              "lane_iters": int(iters.sum()),
              "iters_after_best": int((iters - last).sum())}
    if moves.shape[2] > 2:
        counts["pull_overflow"] = int(moves[:lanes, :, 2].sum())
    return counts


def execute_fm_works(works: Sequence[FMWork],
                     gain_mode: Optional[str] = None,
                     mode: Optional[str] = None
                     ) -> List[Tuple[np.ndarray, float, float]]:
    """Run FM works, one batched dispatch per (n_pad, d_pad) bucket.

    Returns, for each work in input order, the best ``(part, sep_w, imb)``
    across its instances — exactly what ``refine_parts`` returns.  Lane
    results do not depend on which other works share the dispatch, so this
    is equivalent to (but much cheaper than) per-work execution.

    ``mode`` picks the fused on-device pass loop vs the hoisted path
    (default ``ops.fm_mode_default()``, i.e. ``REPRO_FM_MODE``); both
    are bit-identical.  An explicit ``gain_mode`` without an explicit
    ``mode`` forces the hoisted path — the gain backend only exists
    there, and callers comparing gain backends mean to compare them.
    """
    from repro.kernels.ops import fm_mode_default, fm_refine_batch
    if mode is None:
        mode = "hoisted" if gain_mode is not None else fm_mode_default()
    if mode == "hoisted" and gain_mode is None:
        gain_mode = gain_mode_default()
    results: List[Optional[Tuple[np.ndarray, float, float]]] = \
        [None] * len(works)
    groups = defaultdict(list)
    for i, w in enumerate(works):
        groups[w.bucket_key()].append(i)
    for (n_pad, d_pad, passes, pos_only), idxs in groups.items():
        # the oracle rung is the reference on the whole table
        full = (max(_pow2(works[i].nbr.shape[1], 8) for i in idxs)
                if mode == "oracle" else None)
        lanes = [_prepare_lanes(works[i], full) for i in idxs]
        counts = [ln.parts0.shape[0] for ln in lanes]
        L_real = sum(counts)
        # Lane padding to a multiple of 8: dead lanes still pay the vmapped
        # move-loop body every trip, so pow2 padding would waste up to 2×.
        L_pad = -(-L_real // 8) * 8
        pad = L_pad - L_real

        def cat(get, fill_from_first):
            arrs = [get(ln) for ln in lanes]
            if pad:
                arrs.append(np.broadcast_to(get(lanes[0])[:1],
                                            (pad,) + get(lanes[0]).shape[1:])
                            if fill_from_first else
                            np.zeros((pad,) + arrs[0].shape[1:],
                                     arrs[0].dtype))
            return np.concatenate(arrs, axis=0)

        nbr_b = cat(lambda ln: ln.nbr, True)
        vw_b = cat(lambda ln: ln.vwgt, True)
        lock_b = cat(lambda ln: ln.locked, True)
        parts_b = cat(lambda ln: ln.parts0, True)
        keys_b = cat(lambda ln: ln.keys, True)
        eps_b = cat(lambda ln: ln.eps, True)
        mm_b = cat(lambda ln: ln.max_moves, False)  # dummies: 0 moves
        np_b = cat(lambda ln: ln.n_pert, True)
        sid_b = cat(lambda ln: ln.side_id, True)
        scnt_b = cat(lambda ln: ln.side_cnt, True)
        from repro import obs
        from repro.core.dgraph import _note_launch

        def dispatch():
            return jax.device_get(fm_refine_batch(
                jnp.asarray(nbr_b), jnp.asarray(vw_b), jnp.asarray(parts_b),
                jnp.asarray(lock_b), jnp.asarray(keys_b), jnp.asarray(eps_b),
                jnp.asarray(mm_b), jnp.asarray(np_b), jnp.asarray(sid_b),
                jnp.asarray(scnt_b), passes=passes, pos_only=pos_only,
                mode=mode, gain_mode=gain_mode))

        # the compiled program does not depend on the lanes' move
        # budgets (max_moves is traced lane data in both modes), so the
        # jit key — which decides the compile/dispatch billing split —
        # carries only program-shaping fields.  One dispatch:fm span
        # covers all ``passes`` on-device passes of the bucket.
        parts, sep_w, imb, moves = obs.timed_dispatch(
            "fm", "fm",
            ("fm", mode, n_pad, full or d_pad, passes, pos_only, gain_mode,
             L_pad),
            dispatch, lanes=L_real, lanes_pad=L_pad, mode=mode,
            max_moves=int(mm_b.max()),
            bucket=(n_pad, d_pad, passes, pos_only))
        # real neighbour slots of the real lanes, from the unpadded
        # tables less their side rows, and the lanes that have side rows
        slots = sum(works[i].split.slots * k for i, k in zip(idxs, counts))
        side_lanes = sum(k for i, k in zip(idxs, counts)
                         if len(works[i].split.rows))
        _note_launch("fm", 0, L_real, L_pad,
                     (n_pad, d_pad, passes, pos_only), passes, 0,
                     slots=slots, side_lanes=side_lanes,
                     **_launch_counts(moves, L_real))
        off = 0
        for i, k in zip(idxs, counts):
            n = works[i].nbr.shape[0]
            results[i] = _select_best(
                works[i], parts[off:off + k, :n],
                sep_w[off:off + k], imb[off:off + k])
            off += k
    return results                                           # type: ignore


def refine_parts(nbr: np.ndarray, vwgt: np.ndarray, part: np.ndarray,
                 locked: np.ndarray, seed: int, k_inst: int = 8,
                 eps_frac: float = 0.1, passes: int = 3,
                 max_moves: int | None = None, n_pert: int = 8,
                 parts_init: np.ndarray | None = None,
                 pos_only: bool = False
                 ) -> Tuple[np.ndarray, float, float]:
    """Run K FM instances on an ELL graph; return the best part vector.

    Selection is the paper's: best refined band separator wins —
    min separator weight among balance-feasible instances.
    ``parts_init`` optionally provides a distinct initial state per instance
    (K, n) — used by the initial-partition phase.  This is the one-work
    convenience wrapper over ``execute_fm_works``.
    """
    work = FMWork(nbr=nbr, vwgt=vwgt, part=part, locked=locked, seed=seed,
                  k_inst=k_inst, eps_frac=eps_frac, passes=passes,
                  max_moves=max_moves, n_pert=n_pert, parts_init=parts_init,
                  pos_only=pos_only)
    return execute_fm_works([work])[0]


def separator_is_valid(nbr: np.ndarray, part: np.ndarray) -> bool:
    """No edge joins part 0 and part 1."""
    valid = nbr >= 0
    pn = np.where(valid, part[np.where(valid, nbr, 0)], 3)
    p = part[:, None]
    bad = ((p == 0) & (pn == 1)) | ((p == 1) & (pn == 0))
    return not bool(bad.any())
