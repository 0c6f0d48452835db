"""Pure-jnp oracles for the Pallas kernels (the correctness contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ell_spmv_ref(nbr: jax.Array, val: jax.Array, x: jax.Array) -> jax.Array:
    mask = nbr >= 0
    idx = jnp.where(mask, nbr, 0)
    xv = x[idx]
    acc = jnp.sum(jnp.where(mask, val * xv, 0).astype(jnp.float32), axis=1)
    return acc.astype(x.dtype)


def bfs_multi_ref(nbr: jax.Array, src: jax.Array, width: int) -> jax.Array:
    """Batched min-plus BFS relaxation (oracle for band_batch.bfs_multi)."""
    UNREACH = jnp.int32(2 ** 30)
    L, n, d = nbr.shape
    valid = nbr >= 0
    idx = jnp.where(valid, nbr, 0)
    dist = jnp.where(src != 0, 0, UNREACH).astype(jnp.int32)
    for _ in range(width):
        dn = jnp.take_along_axis(dist, idx.reshape(L, n * d),
                                 axis=1).reshape(L, n, d)
        dn = jnp.where(valid, dn, UNREACH)
        dist = jnp.minimum(dist, jnp.min(dn, axis=2) + 1)
    return dist


def sep_gain_multi_ref(nbr: jax.Array, vwgt: jax.Array, part: jax.Array):
    """Batched pulled-weight gains (oracle for band_batch.sep_gain_multi)."""
    L, n, d = nbr.shape
    valid = nbr >= 0
    flat = jnp.where(valid, nbr, 0).reshape(L, n * d)
    pn = jnp.take_along_axis(part, flat, axis=1).reshape(L, n, d)
    wn = jnp.take_along_axis(vwgt.astype(jnp.float32), flat,
                             axis=1).reshape(L, n, d)
    wn = jnp.where(valid, wn, 0.0)
    return (jnp.sum(wn * (pn == 1), axis=2),
            jnp.sum(wn * (pn == 0), axis=2))


def fm_fused_ref(nbr: jax.Array, vwgt: jax.Array, parts_init: jax.Array,
                 locked: jax.Array, noise: jax.Array, eps_abs: jax.Array,
                 max_moves: jax.Array, n_pert: jax.Array, passes: int = 3,
                 pos_only: bool = False):
    """Oracle for the fused FM pass loop (``fm_fused.fm_fused_multi``).

    An independent jnp implementation — it shares no code with the
    kernel or the hoisted path, which is what makes the differential
    parity suite (``tests/test_fm_fused.py``) meaningful.  Takes the
    kernel's *device* inputs: precomputed tiebreak ``noise``
    (L, passes, 2, n) from ``fm_fused.fm_noise`` and absolute balance
    slack ``eps_abs`` (L,).  All float sums are over integer-valued
    float32 weights, so any reduction order is exact and bit-parity with
    the kernel is well-defined.  Returns (parts int8, sep_w, imb, moves):
    ``moves`` (L, passes, 2) holds each pass's moves run and one past its
    last improving move (0 if none).
    """
    L, n, d = nbr.shape

    def one_lane(nbr, vwgt_f, part, locked, noise_all, eps_abs,
                 max_moves, n_pert):
        valid = nbr >= 0
        nbrs = jnp.where(valid, nbr, 0)

        def sums(part):
            return (jnp.sum(vwgt_f * (part == 0)),
                    jnp.sum(vwgt_f * (part == 1)),
                    jnp.sum(vwgt_f * (part == 2)))

        def move_body(carry):
            (i, alive, part, moved, pulled0, pulled1,
             w0, w1, ws, bpart, bws, bimb, noise, pert, last) = carry
            imb = jnp.abs(w0 - w1)
            feas0 = jnp.abs((w0 + vwgt_f) - (w1 - pulled0)) \
                <= jnp.maximum(eps_abs, imb)
            feas1 = jnp.abs((w0 - pulled1) - (w1 + vwgt_f)) \
                <= jnp.maximum(eps_abs, imb)
            movable = (part == 2) & ~moved & ~locked
            ok0, ok1 = movable & feas0, movable & feas1
            if pos_only:
                ok0 = ok0 & (vwgt_f - pulled0 > 0)
                ok1 = ok1 & (vwgt_f - pulled1 > 0)
            amp = jnp.where(i < pert, 1e9, 1e-3)
            scores = jnp.concatenate([
                jnp.where(ok0, vwgt_f - pulled0 + noise[0] * amp, -jnp.inf),
                jnp.where(ok1, vwgt_f - pulled1 + noise[1] * amp, -jnp.inf)])
            idx = jnp.argmax(scores)
            ok = scores[idx] > -jnp.inf
            side = (idx >= n).astype(part.dtype)
            v = (idx % n).astype(jnp.int32)
            nv, nvalid = nbrs[v], valid[v]
            pull = nvalid & (part[nv] == (1 - side)) & ok
            pulled_w = jnp.sum(jnp.where(pull, vwgt_f[nv], 0.0))
            part = part.at[jnp.where(pull, nv, n)].set(2, mode="drop")
            part = part.at[v].set(jnp.where(ok, side, part[v]))
            tgt_v = jnp.where(nvalid & ok, nv, n)
            dv_w = vwgt_f[v]
            pulled0 = pulled0.at[tgt_v].add(
                jnp.where(side == 1, dv_w, 0.0), mode="drop")
            pulled1 = pulled1.at[tgt_v].add(
                jnp.where(side == 0, dv_w, 0.0), mode="drop")
            rows = nbrs[nv]
            rvalid = valid[nv] & pull[:, None]
            tgt_u = jnp.where(rvalid, rows, n).reshape(-1)
            amt = jnp.where(rvalid, jnp.broadcast_to(
                vwgt_f[nv][:, None], rows.shape), 0.0).reshape(-1)
            pulled0 = pulled0.at[tgt_u].add(
                jnp.where(side == 0, -amt, 0.0), mode="drop")
            pulled1 = pulled1.at[tgt_u].add(
                jnp.where(side == 1, -amt, 0.0), mode="drop")
            dv = jnp.where(ok, dv_w, 0.0)
            w0 = w0 + jnp.where(side == 0, dv, 0.0) \
                - jnp.where(side == 1, pulled_w, 0.0)
            w1 = w1 + jnp.where(side == 1, dv, 0.0) \
                - jnp.where(side == 0, pulled_w, 0.0)
            ws = ws - dv + pulled_w
            moved = moved.at[v].set(moved[v] | ok)
            imb_new = jnp.abs(w0 - w1)
            better = (ws < bws) & (imb_new <= jnp.maximum(eps_abs, bimb))
            bpart = jnp.where(better, part, bpart)
            bws = jnp.where(better, ws, bws)
            bimb = jnp.where(better, jnp.minimum(imb_new, bimb), bimb)
            last = jnp.where(better, i + 1, last)
            return (i + 1, ok, part, moved, pulled0, pulled1,
                    w0, w1, ws, bpart, bws, bimb, noise, pert, last)

        def pass_body(p, carry):
            part, bpart, bws, bimb, moves = carry
            w0, w1, ws = sums(part)
            flat = nbrs.reshape(-1)
            pn = jnp.take(part, flat, axis=0).reshape(nbr.shape)
            wn = jnp.where(valid, jnp.take(vwgt_f, flat,
                                           axis=0).reshape(nbr.shape), 0.0)
            pulled0 = jnp.sum(wn * (pn == 1), axis=1)
            pulled1 = jnp.sum(wn * (pn == 0), axis=1)
            carry0 = (jnp.int32(0), jnp.bool_(True), part,
                      jnp.zeros(n, bool), pulled0, pulled1, w0, w1, ws,
                      bpart, bws, bimb, noise_all[p],
                      jnp.where(p == 0, n_pert, 0), jnp.int32(0))
            out = jax.lax.while_loop(
                lambda c: (c[0] < max_moves) & c[1], move_body, carry0)
            moves = moves.at[p, 0].set(out[0]).at[p, 1].set(out[14])
            return (out[9], out[9], out[10], out[11], moves)  # part <- best

        w0, w1, ws = sums(part)
        carry = (part, part, ws, jnp.abs(w0 - w1),
                 jnp.zeros((passes, 2), jnp.int32))
        part, bpart, bws, bimb, moves = jax.lax.fori_loop(
            0, passes, pass_body, carry)
        return bpart, bws, bimb, moves

    parts, bws, bimb, moves = jax.vmap(one_lane)(
        jnp.asarray(nbr, jnp.int32), vwgt.astype(jnp.float32),
        parts_init.astype(jnp.int32), jnp.asarray(locked, bool),
        noise, eps_abs.astype(jnp.float32),
        jnp.asarray(max_moves, jnp.int32), jnp.asarray(n_pert, jnp.int32))
    return parts.astype(jnp.int8), bws, bimb, moves


def diffusion_step_ref(nbr: jax.Array, val: jax.Array, x: jax.Array,
                       inj: jax.Array, dt: float = 0.25,
                       mu: float = 0.1) -> jax.Array:
    mask = nbr >= 0
    idx = jnp.where(mask, nbr, 0)
    xf = x.astype(jnp.float32)
    wv = jnp.where(mask, val.astype(jnp.float32), 0.0)
    flow = jnp.sum(wv * xf[idx], axis=1)
    deg = jnp.sum(wv, axis=1)
    y = (xf + dt * (flow - deg * xf) - dt * mu * jnp.sign(xf)
         + inj.astype(jnp.float32))
    return y.astype(x.dtype)
