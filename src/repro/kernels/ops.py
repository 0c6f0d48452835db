"""Public jit'd wrappers around the device kernels (bucketed dispatch API).

Pallas wrappers handle padding to block multiples and backend selection:
``interpret=True`` (Python execution of the kernel body) on CPU hosts,
compiled Mosaic on TPU.  The batched entry points (``band_bfs_batch``,
``sep_gain_batch``, ``match_batch``) are what the service's bucketed
executors dispatch — one call per shape bucket, lanes mixing independent
subproblems.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.band_batch import bfs_multi, sep_gain_multi
from repro.kernels.diffusion import diffusion_step
from repro.kernels.ell_spmv import ell_spmv
from repro.kernels.fm_fused import fm_fused_multi


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def fm_mode_default() -> str:
    """FM refinement path: REPRO_FM_MODE=fused|hoisted|auto.

    ``fused`` runs the whole pass loop on device as one Pallas kernel
    (``kernels.fm_fused``); ``hoisted`` is the pre-fusion path
    (``core.fm.fm_refine_multi``: Python pass loop traced into one XLA
    program, batched gain recompute per pass).  The two are
    bit-identical (asserted in ``tests/test_fm_fused.py``).  ``auto``
    resolves by platform: ``hoisted`` on TPU, because the v5e compiler
    refuses the fused kernel (its ``(1, n)`` blocks break the (8, 128)
    block rule, and its body gathers, scatters and takes an int32
    argmax, none of which Mosaic lowers); ``fused`` elsewhere, where it
    runs in interpret mode and measured faster than ``hoisted``.
    """
    mode = os.environ.get("REPRO_FM_MODE", "auto")
    if mode == "auto":
        return "hoisted" if jax.default_backend() == "tpu" else "fused"
    return mode


def fm_refine_batch(nbr, vwgt, parts_init, locked, keys, eps_frac,
                    max_moves, n_pert, side_id, side_cnt, passes: int = 3,
                    pos_only: bool = False, mode: str | None = None,
                    gain_mode: str | None = None,
                    interpret: bool | None = None):
    """Batched FM refinement over a bucket's lane stack (mode-switched).

    The single entry point ``core.fm.execute_fm_works`` dispatches
    through — shapes as in ``fm_refine_multi``.  ``mode`` selects the
    fused kernel vs the hoisted path (default ``fm_mode_default()``);
    ``oracle`` is the independent jnp reference (``kernels.ref``) — the
    recovery ladder's last kernel rung (DESIGN.md §8), sharing no code
    with the other two; it ignores ``side_id`` / ``side_cnt`` and takes
    the whole table, side rows included.  ``gain_mode`` only applies to
    the hoisted path's per-pass gain recompute backend.  All modes return
    bit-identical results, ``(parts, sep_w, imb, moves)``, the move
    counters included (asserted in ``tests/test_fm_fused.py`` and
    ``tests/test_fm_counters.py``); the oracle's ``moves`` lacks the
    third counter, ``pull_overflow``, which only the compacted update has.
    """
    if mode is None:
        mode = fm_mode_default()
    if mode == "fused":
        if interpret is None:
            interpret = _interpret_default()
        return fm_fused_multi(nbr, vwgt, parts_init, locked, keys,
                              eps_frac, max_moves, n_pert, side_id,
                              side_cnt, passes=passes, pos_only=pos_only,
                              interpret=interpret)
    if mode == "oracle":
        from repro.kernels.fm_fused import fm_noise
        from repro.kernels.ref import fm_fused_ref
        nbr = jnp.asarray(nbr, jnp.int32)
        vwgt = jnp.asarray(vwgt)
        noise = fm_noise(jnp.asarray(keys), nbr.shape[1], passes)
        eps_abs = jnp.asarray(eps_frac) * \
            vwgt.astype(jnp.float32).sum(axis=1)
        return fm_fused_ref(nbr, vwgt, jnp.asarray(parts_init),
                            jnp.asarray(locked), noise, eps_abs,
                            jnp.asarray(max_moves), jnp.asarray(n_pert),
                            passes=passes, pos_only=pos_only)
    if mode != "hoisted":
        raise ValueError(f"REPRO_FM_MODE={mode!r} not in "
                         "fused|hoisted|oracle|auto")
    from repro.core.fm import fm_refine_multi, gain_mode_default
    if gain_mode is None:
        gain_mode = gain_mode_default()
    return fm_refine_multi(nbr, vwgt, parts_init, locked, keys, eps_frac,
                           max_moves, n_pert, side_id, side_cnt,
                           passes=passes, pos_only=pos_only,
                           gain_mode=gain_mode)


def ell_relax_step(nbr: jax.Array, dist_ext: jax.Array, big) -> jax.Array:
    """One min-plus ELL relaxation: min over valid neighbors of ext+1.

    ``nbr`` (n, d) compact ids with -1 padding; ``dist_ext`` is any vector
    the ids index into — the distance vector itself in the centralized BFS
    (``core.band``), or the halo-extended local+ghost vector in the
    distributed sweep (``core.dgraph``).  Shared so the two sweeps relax
    identically.

    Lane-stacked form: ``nbr`` (L, n, d) with ``dist_ext`` (L, m) relaxes
    every lane against its own extended vector — the per-bucket stacked
    BFS of ``dgraph.distributed_bfs_stacked`` runs all lanes of a wave
    through one such step per relaxation.  Reductions stay within-lane,
    so each lane equals its 2-D singleton relaxation bit-for-bit.
    """
    valid = nbr >= 0
    idx = jnp.where(valid, nbr, 0)
    if nbr.ndim == 3:
        L, n, d = nbr.shape
        dn = jnp.take_along_axis(dist_ext, idx.reshape(L, n * d),
                                 axis=1).reshape(L, n, d)
        dn = jnp.where(valid, dn, big)
    else:
        dn = jnp.where(valid, dist_ext[idx], big)
    return jnp.min(dn, axis=-1) + 1


def _pad_rows(a: np.ndarray | jax.Array, block: int, fill):
    n = a.shape[0]
    pad = (-n) % block
    if pad == 0:
        return a, n
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=fill), n


def spmv(nbr, val, x, block_rows: int = 256, interpret: bool | None = None):
    """ELL SpMV with automatic padding; returns (n,) like x."""
    if interpret is None:
        interpret = _interpret_default()
    n = x.shape[0]
    nbr_p, _ = _pad_rows(jnp.asarray(nbr, jnp.int32), block_rows, -1)
    val_p, _ = _pad_rows(jnp.asarray(val), block_rows, 0)
    # x stays unpadded except to match row padding (gather targets < n)
    x_p, _ = _pad_rows(jnp.asarray(x), block_rows, 0)
    y = ell_spmv(nbr_p, val_p, x_p, block_rows=block_rows,
                 interpret=interpret)
    return y[:n]


def band_bfs_batch(nbr, src, width: int, interpret: bool | None = None):
    """Batched band-distance sweep over a bucket of ELL graphs.

    nbr (L, n, d) int32 / src (L, n) bool-ish → dist (L, n) int32 clipped
    at width+1 (UNREACH beyond).  One kernel launch for the whole bucket.
    """
    if interpret is None:
        interpret = _interpret_default()
    return bfs_multi(jnp.asarray(nbr, jnp.int32),
                     jnp.asarray(src, jnp.int32), width,
                     interpret=interpret)


def match_batch(nbr, wgt, keys, rounds: int = 8):
    """Batched heavy-edge matching over a bucket of ELL graphs.

    nbr/wgt (L, n, d) int32 (-1 / 0 pad), keys (L, 2) uint32 PRNG keys →
    match (L, n) int32 (mate id, self for singletons).  One vmapped XLA
    dispatch for the whole bucket; per-lane results equal the single-graph
    ``matching.heavy_edge_matching`` with the same key.
    """
    from repro.core.matching import heavy_edge_matching_multi
    return heavy_edge_matching_multi(jnp.asarray(nbr, jnp.int32),
                                     jnp.asarray(wgt, jnp.int32),
                                     jnp.asarray(keys), rounds=rounds)


def sep_gain_batch(nbr, vwgt, part, block_rows: int = 256,
                   interpret: bool | None = None):
    """Batched separator FM gain recompute (pulled weights), (L, n) pair."""
    if interpret is None:
        interpret = _interpret_default()
    n = nbr.shape[1]
    return sep_gain_multi(jnp.asarray(nbr, jnp.int32),
                          jnp.asarray(vwgt, jnp.float32),
                          jnp.asarray(part, jnp.int32),
                          block_rows=min(block_rows, n), interpret=interpret)


def diffuse(nbr, val, x, inj, steps: int = 1, dt: float = 0.25,
            mu: float = 0.1, block_rows: int = 256,
            interpret: bool | None = None):
    """Run ``steps`` fused diffusion steps; returns final x."""
    if interpret is None:
        interpret = _interpret_default()
    n = x.shape[0]
    nbr_p, _ = _pad_rows(jnp.asarray(nbr, jnp.int32), block_rows, -1)
    val_p, _ = _pad_rows(jnp.asarray(val), block_rows, 0)
    x_p, _ = _pad_rows(jnp.asarray(x), block_rows, 0)
    inj_p, _ = _pad_rows(jnp.asarray(inj), block_rows, 0)
    for _ in range(steps):
        x_p = diffusion_step(nbr_p, val_p, x_p, inj_p, dt=dt, mu=mu,
                             block_rows=block_rows, interpret=interpret)
    return x_p[:n]
