"""Fused on-device FM pass loop (one Pallas kernel per bucket dispatch).

The hoisted path (``core.fm.fm_refine_multi``) traces the pass loop in
Python: each pass is a batched gain recompute plus a vmapped move loop,
unrolled ``passes`` times into one XLA program.  This kernel puts the
pass loop itself on device — grid ``(L,)``, one lane per FM instance,
with the per-lane ``(part, w0, w1, best)`` state resident in VMEM across
all passes:

    HBM:   nbr[l]  vwgt[l]  part0[l]  locked[l]  noise[l]  scalars[l]
             │ (Pallas grid pipeline: lane l+1's blocks stream in while
             ▼  lane l computes — automatic double-buffering)
    VMEM:  ┌────────────────────────────────────────────────┐
           │ fori_loop over passes:                         │
           │   gain recompute (take-based, O(n·d), local)   │
           │   while_loop moves (select → apply → best)     │
           │ state (part, pulled, w0, w1, best) resident    │
           └────────────────────────────────────────────────┘
             ▼
    HBM:   bpart[l]  sep_w[l]  imb[l]  moves[l]

Move budgets are **adaptive per lane**: ``max_moves`` rides in as lane
data (an ``(L, 1)`` input), so each lane's move loop terminates at its
own budget — lanes with small budgets are not serialized behind large
ones, and ``FMWork.bucket_key`` no longer needs the pow2 ``max_moves``
sub-bucket (fewer buckets ⇒ fewer compiles, wider lane stacks).

Each move updates the gains ``pulled`` (the ``(2, n)`` weights each
vertex would pull, per side) in one scatter-add over the flattened
``2n`` array: v's row, plus the rows of the vertices v pulls into the
separator.  Where rows are wider than ``PULL_K`` the pulled slots are
compacted to lists of ``PULL_K`` rows, one scatter round each, so a move
scatters ``(PULL_K + 1) · d`` addends, not the ``d × d`` block of every
slot of v's row.

Move counters: each pass's move loop also reports how many moves it ran
(``iters``), one past the last move that improved the best state
(``last_better``, 0 if none did; the moves after it are thrown away by
the revert to best) and how many moves took more than one scatter round
(``pull_overflow``).  They are computed on every call — scalars in the
loop carry — and returned beside the partitions as ``moves``
``(L, passes, 3)`` int32.

Bit-parity contract: per-pass tiebreak noise is precomputed outside the
kernel (``fm_noise``) with the exact op sequence of the hoisted path —
``jax.random`` cannot run inside a Mosaic kernel — and every float sum
here is over integer-valued float32 vertex weights, hence exact in any
reduction order.  The kernel is therefore bit-identical to the hoisted
path and to the jnp oracle (``kernels.ref.fm_fused_ref``), asserted
across the bucketing space in ``tests/test_fm_fused.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -jnp.inf
BIG_NOISE = 1e9
#: Width of the pulled list one scatter round of the move loop carries.
#: Rows of width ``d <= PULL_K`` take the whole row as the list.
PULL_K = 8


def fm_move_loop(nbrs, valid, vwgt_f, locked, eps_abs, part, pulled0,
                 pulled1, w0, w1, ws, bpart, bws, bimb, noise, pert,
                 max_moves, pos_only: bool = False):
    """One FM pass (a bounded sequence of moves) on a single lane.

    The per-lane data-plane primitive shared by the hoisted path (under
    ``jax.vmap`` in ``core.fm.fm_refine_multi``) and the fused kernel
    (called per grid lane inside ``_fm_fused_kernel``) — one definition,
    so the two paths cannot drift.  Returns the pass's state, then its
    move counters ``iters`` (moves run), ``last_better`` (one past the
    last move that improved the best state, 0 if none did) and
    ``pull_overflow`` (moves whose pulled set took more than one scatter
    round, i.e. pulled more than ``PULL_K`` slots).
    """
    n, d = nbrs.shape
    compact = d > PULL_K

    def move_cond(carry):
        i, alive, *_ = carry
        return (i < max_moves) & alive

    def move_body(carry):
        """One FM move.  ``pulled`` = (pulled0, pulled1) is maintained
        incrementally: selection is O(n) vector ops; the update is one
        scatter-add into the flattened ``(2n,)`` array of v's row (into
        ``pulled[1 - side]``) and the rows of the vertices v pulls (out of
        ``pulled[side]``).  Where ``d > PULL_K`` the pull slots of v's row
        are compacted (cumsum rank, dense ``(PULL_K, d)`` compare) to
        ``PULL_K`` rows per round, in ``ceil(pulled slots / PULL_K)``
        rounds; a duplicated slot stays a row of its own, so every target
        gets the same addends as a full ``d x d`` update."""
        (i, alive, part, moved, pulled, w0, w1, ws, bpart, bws, bimb,
         last_better, overflow) = carry
        pulled0, pulled1 = pulled[0], pulled[1]
        gain0 = vwgt_f - pulled0
        gain1 = vwgt_f - pulled1
        # --- feasibility (balance after move)
        imb = jnp.abs(w0 - w1)
        imb0 = jnp.abs((w0 + vwgt_f) - (w1 - pulled0))
        imb1 = jnp.abs((w0 - pulled1) - (w1 + vwgt_f))
        feas0 = imb0 <= jnp.maximum(eps_abs, imb)
        feas1 = imb1 <= jnp.maximum(eps_abs, imb)
        movable = (part == 2) & ~moved & ~locked
        amp = jnp.where(i < pert, BIG_NOISE, 1e-3)
        ok0, ok1 = movable & feas0, movable & feas1
        if pos_only:                    # ParMETIS-style strict improvement
            ok0, ok1 = ok0 & (gain0 > 0), ok1 & (gain1 > 0)
        s0 = jnp.where(ok0, gain0 + noise[0] * amp, NEG_INF)
        s1 = jnp.where(ok1, gain1 + noise[1] * amp, NEG_INF)
        scores = jnp.concatenate([s0, s1])
        idx = jnp.argmax(scores)
        ok = scores[idx] > NEG_INF
        side = (idx >= n).astype(part.dtype)
        v = (idx % n).astype(jnp.int32)
        # --- apply (masked; no-op when not ok)
        nv = nbrs[v]                                        # (d,)
        nvalid = valid[v]
        pull_slot = nvalid & (part[nv] == (1 - side)) & ok  # pulled set ⊆ N(v)
        pulled_w = jnp.sum(jnp.where(pull_slot, vwgt_f[nv], 0.0))
        # part updates
        tgt_pull = jnp.where(pull_slot, nv, n)
        part = part.at[tgt_pull].set(2, mode="drop")
        part = part.at[v].set(jnp.where(ok, side, part[v]))
        # pulled updates: flat index s * n + x is pulled[s][x]; 2n drops
        to_v = jnp.where(side == 1, 0, n)       # v: 2 -> side
        to_u = n - to_v                         # u: 1 - side -> 2
        dv_w = vwgt_f[v]
        v_idx = jnp.where(nvalid & ok, to_v + nv, 2 * n)
        v_amt = jnp.broadcast_to(dv_w, (d,))

        def rows_of(u, has):
            """Flat targets and addends of the rows of pulled ``u``."""
            rvalid = valid[u] & has[:, None]
            return (jnp.where(rvalid, to_u + nbrs[u], 2 * n),
                    jnp.where(rvalid, -vwgt_f[u][:, None], 0.0))

        def scatter(flat, idx, amt):
            return flat.at[idx.reshape(-1)].add(amt.reshape(-1), mode="drop")

        n_pull = jnp.sum(pull_slot.astype(jnp.int32))
        if compact:
            rank = jnp.cumsum(pull_slot.astype(jnp.int32)) - 1
            row = jnp.arange(PULL_K, dtype=jnp.int32)[:, None]

            def chunk(r):
                """Rows of the pull slots ranked r·K .. r·K + K - 1."""
                sel = pull_slot & (rank == r * PULL_K + row)   # (K, d)
                u = jnp.sum(jnp.where(sel, nv, 0), axis=1)
                return rows_of(u, jnp.any(sel, axis=1))

            u_idx, u_amt = chunk(0)
        else:
            u_idx, u_amt = rows_of(nv, pull_slot)
        flat = scatter(pulled.reshape(-1),
                       jnp.concatenate([v_idx[None], u_idx]),
                       jnp.concatenate([v_amt[None], u_amt]))
        if compact:
            _, flat = jax.lax.while_loop(
                lambda c: c[0] * PULL_K < n_pull,
                lambda c: (c[0] + 1, scatter(c[1], *chunk(c[0]))),
                (jnp.int32(1), flat))
        pulled = flat.reshape(2, n)
        # weights
        dv = jnp.where(ok, dv_w, 0.0)
        w0 = w0 + jnp.where(side == 0, dv, 0.0) - jnp.where(side == 1, pulled_w, 0.0)
        w1 = w1 + jnp.where(side == 1, dv, 0.0) - jnp.where(side == 0, pulled_w, 0.0)
        ws = ws - dv + pulled_w
        moved = moved.at[v].set(moved[v] | ok)
        # --- best-seen tracking (feasible states only)
        imb_new = jnp.abs(w0 - w1)
        better = (ws < bws) & (imb_new <= jnp.maximum(eps_abs, bimb))
        bpart = jnp.where(better, part, bpart)
        bws = jnp.where(better, ws, bws)
        bimb = jnp.where(better, jnp.minimum(imb_new, bimb), bimb)
        last_better = jnp.where(better, i + 1, last_better)
        overflow = overflow + (n_pull > PULL_K).astype(jnp.int32)
        return (i + 1, ok, part, moved, pulled, w0, w1, ws, bpart, bws,
                bimb, last_better, overflow)

    moved = jnp.zeros(n, bool)
    carry = (jnp.int32(0), jnp.bool_(True), part, moved,
             jnp.stack([pulled0, pulled1]), w0, w1, ws, bpart, bws, bimb,
             jnp.int32(0), jnp.int32(0))
    carry = jax.lax.while_loop(move_cond, move_body, carry)
    (iters, _, part, _, _, w0, w1, ws, bpart, bws, bimb,
     last_better, overflow) = carry
    return part, w0, w1, ws, bpart, bws, bimb, iters, last_better, overflow


def fm_noise(keys, n: int, passes: int) -> jax.Array:
    """Per-pass tiebreak noise for all lanes: (L, passes, 2, n).

    Exactly the key-split / uniform op sequence of the hoisted pass loop
    (split once per pass, draw (2, n) from the subkey), hoisted out of
    the kernel because ``jax.random`` cannot run inside Mosaic — values
    are bit-identical to what ``fm_refine_multi`` draws per pass.
    """
    noises = []
    for _ in range(passes):
        both = jax.vmap(jax.random.split)(keys)             # (L, 2, 2)
        keys, subs = both[:, 0], both[:, 1]
        noises.append(jax.vmap(lambda k: jax.random.uniform(k, (2, n)))(subs))
    return jnp.stack(noises, axis=1)


def _fm_fused_kernel(nbr_ref, vwgt_ref, part_ref, locked_ref, noise_ref,
                     eps_ref, mm_ref, np_ref, part_out, bws_out, bimb_out,
                     moves_out, *, passes, pos_only):
    nbr = nbr_ref[0]                          # (n, d) int32, lane-resident
    n, d = nbr.shape
    valid = nbr >= 0
    nbrs = jnp.where(valid, nbr, 0)
    vwgt_f = vwgt_ref[0]                      # (n,) f32
    locked = locked_ref[0] != 0
    noise_all = noise_ref[0]                  # (passes, 2, n)
    eps_abs = eps_ref[0, 0]                   # per-lane scalars ride as
    max_moves = mm_ref[0, 0]                  # (1, 1) blocks (adaptive
    n_pert = np_ref[0, 0]                     # budget = lane data)
    part = part_ref[0]                        # (n,) int32

    def sums(part):
        w0 = jnp.sum(vwgt_f * (part == 0))
        w1 = jnp.sum(vwgt_f * (part == 1))
        ws = jnp.sum(vwgt_f * (part == 2))
        return w0, w1, ws

    w0, w1, ws = sums(part)
    bpart, bws, bimb = part, ws, jnp.abs(w0 - w1)

    def pass_body(p, carry):
        part, w0, w1, ws, bpart, bws, bimb, moves = carry
        noise = jax.lax.dynamic_index_in_dim(noise_all, p, 0,
                                             keepdims=False)   # (2, n)
        pert = jnp.where(p == 0, n_pert, 0)    # perturb pass 1 only
        # gain recompute, VMEM-local (same math as sep_gain_multi)
        flat = nbrs.reshape(-1)
        pn = jnp.take(part, flat, axis=0).reshape(nbr.shape)
        wn = jnp.take(vwgt_f, flat, axis=0).reshape(nbr.shape)
        wn = jnp.where(valid, wn, 0.0)
        pulled0 = jnp.sum(wn * (pn == 1), axis=1)
        pulled1 = jnp.sum(wn * (pn == 0), axis=1)
        (part, w0, w1, ws, bpart, bws, bimb, *counts) = fm_move_loop(
            nbrs, valid, vwgt_f, locked, eps_abs, part, pulled0, pulled1,
            w0, w1, ws, bpart, bws, bimb, noise, pert, max_moves,
            pos_only=pos_only)
        moves = moves.at[p].set(jnp.stack(counts))
        part = bpart                           # revert to best
        w0, w1, ws = sums(part)
        return (part, w0, w1, ws, bpart, bws, bimb, moves)

    moves = jnp.zeros((passes, 3), jnp.int32)
    carry = (part, w0, w1, ws, bpart, bws, bimb, moves)
    carry = jax.lax.fori_loop(0, passes, pass_body, carry)
    (part, w0, w1, ws, bpart, bws, bimb, moves) = carry
    part_out[0] = bpart
    bws_out[0, 0] = bws
    bimb_out[0, 0] = bimb
    moves_out[0] = moves


@functools.partial(jax.jit, static_argnames=("passes", "pos_only",
                                             "interpret"))
def fm_fused_multi(nbr, vwgt, parts_init, locked, keys, eps_frac,
                   max_moves, n_pert, passes: int = 3,
                   pos_only: bool = False, interpret: bool = True):
    """Fused FM over a flat lane axis — the on-device pass loop.

    Same contract and shapes as ``core.fm.fm_refine_multi`` (L = lanes):
    nbr (L, n, d) int32; vwgt (L, n); parts_init (L, n) int8; locked
    (L, n) bool; keys (L, 2) uint32; eps_frac (L,) f32; max_moves,
    n_pert (L,) int32.  Returns (parts int8, sep_w, imb, moves),
    bit-identical to the hoisted path.  The compiled program does not
    depend on ``max_moves`` (traced lane data), so works with different
    budgets share one executable.
    """
    L, n, d = nbr.shape
    vwgt_f = vwgt.astype(jnp.float32)
    eps_abs = eps_frac.astype(jnp.float32) * vwgt_f.sum(axis=1)
    noise = fm_noise(keys, n, passes)                       # (L, passes, 2, n)
    parts, bws, bimb, moves = pl.pallas_call(
        functools.partial(_fm_fused_kernel, passes=passes,
                          pos_only=pos_only),
        grid=(L,),
        in_specs=[
            pl.BlockSpec((1, n, d), lambda l: (l, 0, 0)),
            pl.BlockSpec((1, n), lambda l: (l, 0)),
            pl.BlockSpec((1, n), lambda l: (l, 0)),
            pl.BlockSpec((1, n), lambda l: (l, 0)),
            pl.BlockSpec((1, passes, 2, n), lambda l: (l, 0, 0, 0)),
            pl.BlockSpec((1, 1), lambda l: (l, 0)),
            pl.BlockSpec((1, 1), lambda l: (l, 0)),
            pl.BlockSpec((1, 1), lambda l: (l, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n), lambda l: (l, 0)),
            pl.BlockSpec((1, 1), lambda l: (l, 0)),
            pl.BlockSpec((1, 1), lambda l: (l, 0)),
            pl.BlockSpec((1, passes, 3), lambda l: (l, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, n), jnp.int32),
            jax.ShapeDtypeStruct((L, 1), jnp.float32),
            jax.ShapeDtypeStruct((L, 1), jnp.float32),
            jax.ShapeDtypeStruct((L, passes, 3), jnp.int32),
        ],
        interpret=interpret,
    )(nbr, vwgt_f, parts_init.astype(jnp.int32),
      locked.astype(jnp.int32), noise,
      eps_abs[:, None], max_moves[:, None], n_pert[:, None])
    return parts.astype(jnp.int8), bws[:, 0], bimb[:, 0], moves
