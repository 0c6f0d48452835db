"""Differential parity harness for the fused on-device FM pass loop.

Three implementations of the same refinement must be bit-identical
(parts, sep_w, imb — exact equality, no tolerance):

* the fused Pallas kernel (``kernels.fm_fused.fm_fused_multi``, the
  production path, run here in interpret mode on CPU);
* the hoisted reference path (``core.fm.fm_refine_multi``: Python pass
  loop, batched gain recompute per pass — the pre-fusion pipeline);
* the independent jnp oracle (``kernels.ref.fm_fused_ref``, which
  shares no code with either).

Exactness is well-defined because vertex weights are integer-valued
float32, so every sum in the pipeline is exact regardless of reduction
order, and the tiebreak noise is drawn by the same key-split sequence
(``fm_fused.fm_noise``) on both paths.

Also here: the bucket-key regression tests for the adaptive per-lane
move budget — ``max_moves`` left ``FMWork.bucket_key()``, so works with
different budgets share one dispatch and must still match their
singleton runs bit-for-bit.
"""
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.fm import (FMWork, execute_fm_works,  # noqa: E402
                           fm_refine_multi, refine_parts)
from repro.kernels.fm_fused import (PULL_K, fm_fused_multi,  # noqa: E402
                                    fm_noise)
from repro.kernels.ops import fm_mode_default  # noqa: E402
from repro.kernels.ref import fm_fused_ref  # noqa: E402


def _rand_lanes(seed: int, L: int, n: int, d: int,
                mixed_budget: bool = True, drop: float = 0.4):
    """A random lane stack: ELL graphs, weights, states, locks, budgets.
    Rows list neighbours with repeats; ``drop`` of the slots are empty."""
    rng = np.random.default_rng(seed)
    nbr = rng.integers(0, n, (L, n, d)).astype(np.int32)
    nbr[rng.random((L, n, d)) < drop] = -1          # ragged rows
    vwgt = rng.integers(1, 4, (L, n)).astype(np.int32)
    part = rng.integers(0, 3, (L, n)).astype(np.int8)
    locked = rng.random((L, n)) < rng.uniform(0.0, 0.3, (L, 1))
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed + 1), L))
    eps = np.full(L, 0.1, np.float32)
    if mixed_budget:                                # adaptive per lane
        mm = rng.integers(3, 2 * n, L).astype(np.int32)
    else:
        mm = np.full(L, n, np.int32)
    n_pert = np.full(L, 8, np.int32)
    return tuple(jnp.asarray(a) for a in
                 (nbr, vwgt, part, locked, keys, eps, mm, n_pert))


def _run_all_three(args, passes: int, pos_only: bool):
    nbr, vwgt, parts0, locked, keys, eps, mm, n_pert = args
    hoisted = fm_refine_multi(*args, passes=passes, pos_only=pos_only,
                              gain_mode="jnp")
    fused = fm_fused_multi(*args, passes=passes, pos_only=pos_only,
                           interpret=True)
    noise = fm_noise(keys, nbr.shape[1], passes)
    eps_abs = eps * vwgt.astype(jnp.float32).sum(axis=1)
    oracle = fm_fused_ref(nbr, vwgt, parts0, locked, noise, eps_abs,
                          mm, n_pert, passes=passes, pos_only=pos_only)
    return hoisted, fused, oracle


def _assert_bit_identical(a, b, what: str):
    for name, x, y in zip(("parts", "sep_w", "imb"), a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert np.array_equal(x, y), \
            f"{what}: {name} differs ({(x != y).sum()} mismatches)"


# ------------------------------------------------------------------ #
# differential sweep: fused == hoisted == oracle, bit-for-bit
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("L", [1, 3, 8])
def test_fused_parity_lane_sweep(L):
    """Seeded sweep over lane counts with mixed locks and mixed per-lane
    move budgets: all three implementations bit-identical."""
    args = _rand_lanes(seed=10 + L, L=L, n=32, d=4)
    hoisted, fused, oracle = _run_all_three(args, passes=3, pos_only=False)
    _assert_bit_identical(fused, hoisted, f"L={L} fused vs hoisted")
    _assert_bit_identical(fused, oracle, f"L={L} fused vs oracle")


@pytest.mark.parametrize("passes,pos_only",
                         [(1, False), (1, True), (3, True)])
def test_fused_parity_passes_and_pos_only(passes, pos_only):
    args = _rand_lanes(seed=7, L=3, n=32, d=4)
    hoisted, fused, oracle = _run_all_three(args, passes=passes,
                                            pos_only=pos_only)
    tag = f"passes={passes} pos_only={pos_only}"
    _assert_bit_identical(fused, hoisted, f"{tag} fused vs hoisted")
    _assert_bit_identical(fused, oracle, f"{tag} fused vs oracle")


@pytest.mark.parametrize("d,n,seed,drop", [(32, 64, 32, 0.1),
                                           (64, 64, 66, 0.1),
                                           (PULL_K, 32, 3, 0.0)])
def test_fused_parity_pulled_rounds(d, n, seed, drop):
    """Dense rows that repeat neighbours: with ``d > PULL_K`` moves pull
    more than ``PULL_K`` slots, so the compacted update runs extra scatter
    rounds; with ``d <= PULL_K`` the whole row is the list and none
    overflows.  All three implementations stay bit-identical, and the
    counters the oracle has (``iters``, ``last_better``) agree."""
    args = _rand_lanes(seed=seed, L=3, n=n, d=d, drop=drop)
    hoisted, fused, oracle = _run_all_three(args, passes=3, pos_only=False)
    _assert_bit_identical(fused, hoisted, f"d={d} fused vs hoisted")
    _assert_bit_identical(fused, oracle, f"d={d} fused vs oracle")
    moves = np.asarray(fused[3])
    assert np.array_equal(moves, np.asarray(hoisted[3]))
    assert np.array_equal(moves[..., :2], np.asarray(oracle[3]))
    overflow = moves[..., 2]
    assert (overflow <= moves[..., 0]).all()
    if d > PULL_K:
        assert overflow.sum() > 0, "no move took a second scatter round"
    else:
        assert (overflow == 0).all()


def test_fused_parity_many_seeds_property_sweep():
    """Property-style: many random graphs through one compiled shape
    (same L/n/d keeps this sweep on the jit cache)."""
    for seed in range(6):
        args = _rand_lanes(seed=100 + seed, L=3, n=32, d=4)
        hoisted, fused, _ = _run_all_three(args, passes=3, pos_only=False)
        _assert_bit_identical(fused, hoisted, f"seed={seed}")


def test_fused_noise_matches_hoisted_key_sequence():
    """The precomputed noise block replays the hoisted path's exact
    split/uniform op sequence — the foundation of bit-parity."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    n, passes = 16, 3
    noise = fm_noise(keys, n, passes)
    assert noise.shape == (4, passes, 2, n)
    k = keys
    for p in range(passes):
        both = jax.vmap(jax.random.split)(k)
        k, subs = both[:, 0], both[:, 1]
        expect = jax.vmap(lambda s: jax.random.uniform(s, (2, n)))(subs)
        assert np.array_equal(np.asarray(noise[:, p]), np.asarray(expect))


# ------------------------------------------------------------------ #
# bucket-key regression: the adaptive per-lane budget
# ------------------------------------------------------------------ #
def _work(n=30, d=4, seed=5, **kw):
    rng = np.random.default_rng(seed)
    nbr = rng.integers(0, n, (n, d)).astype(np.int32)
    nbr[rng.random((n, d)) < 0.3] = -1
    kw.setdefault("vwgt", np.ones(n, np.int64))
    kw.setdefault("part", rng.integers(0, 3, n).astype(np.int8))
    kw.setdefault("locked", np.zeros(n, bool))
    return FMWork(nbr=nbr, seed=seed, **kw)


def test_bucket_key_drops_max_moves_component():
    """Works that differ only in max_moves share one bucket (the _mm
    pow2 sub-bucket is gone); the key is (n_pad, d_pad, passes,
    pos_only)."""
    w_small = _work(max_moves=5)
    w_large = _work(max_moves=500)
    w_default = _work()                     # sep_sz-derived default
    assert w_small.bucket_key() == w_large.bucket_key() \
        == w_default.bucket_key() == (64, 8, 3, False)
    assert w_small.bucket_key() != _work(passes=1).bucket_key()
    assert w_small.bucket_key() != _work(pos_only=True).bucket_key()


def test_effective_max_moves_clamp_edges():
    # n_pad boundary: a budget above the padded vertex count clamps to
    # it (pow2 padding has a floor of 64 rows)
    w = _work(n=30, max_moves=10_000)
    assert w.effective_max_moves() == 64
    w130 = _work(n=130, max_moves=10_000)
    assert w130.effective_max_moves() == 256
    # 4096 cap: huge graphs never compile a larger trip bound
    n_big = 5000
    nbr = -np.ones((n_big, 2), np.int32)
    w_big = FMWork(nbr=nbr, vwgt=np.ones(n_big, np.int64),
                   part=np.full(n_big, 2, np.int8),
                   locked=np.zeros(n_big, bool), seed=0, max_moves=9999)
    assert w_big.effective_max_moves() == 4096
    # sep_sz-derived default: 2·|sep| + 16 when max_moves is None
    part = np.zeros(30, np.int8)
    part[:5] = 2
    w_def = _work(part=part, max_moves=None)
    assert w_def.effective_max_moves() == 2 * 5 + 16
    # ... and the parts_init variant takes the max separator over starts
    starts = np.zeros((2, 30), np.int8)
    starts[1, :7] = 2
    w_multi = _work(part=part, parts_init=starts, max_moves=None)
    assert w_multi.effective_max_moves() == 2 * 7 + 16


@pytest.mark.parametrize("mode", ["fused", "hoisted"])
def test_mixed_budget_bucket_matches_singletons(mode):
    """Lanes with different max_moves share one dispatch and still match
    their singleton runs bit-for-bit — the adaptive-budget invariant."""
    works = [_work(seed=s, max_moves=m)
             for s, m in [(1, 5), (2, 40), (3, None), (4, 4096)]]
    assert len({w.bucket_key() for w in works}) == 1
    batched = execute_fm_works(works, mode=mode)
    singles = [execute_fm_works([w], mode=mode)[0] for w in works]
    for i, (b, s) in enumerate(zip(batched, singles)):
        _assert_bit_identical(b, s, f"work {i} batched vs singleton")


def test_execute_fm_works_mode_parity_and_env_switch(monkeypatch):
    """The executor's fused and hoisted paths agree end-to-end, and
    REPRO_FM_MODE drives the default."""
    works = [_work(seed=s, max_moves=m) for s, m in [(7, 9), (8, 64)]]
    fused = execute_fm_works(works, mode="fused")
    hoisted = execute_fm_works(works, mode="hoisted")
    for i, (f, h) in enumerate(zip(fused, hoisted)):
        _assert_bit_identical(f, h, f"work {i} fused vs hoisted")
    monkeypatch.setenv("REPRO_FM_MODE", "hoisted")
    assert fm_mode_default() == "hoisted"
    monkeypatch.setenv("REPRO_FM_MODE", "auto")
    assert fm_mode_default() == "fused"
    monkeypatch.setenv("REPRO_FM_MODE", "bogus")
    with pytest.raises(ValueError):
        execute_fm_works(works[:1], mode="bogus")


def test_refine_parts_contract_under_fused_default():
    """The one-work convenience wrapper keeps its contract on the fused
    path: padding rows never enter the separator, output is a valid
    3-state labeling."""
    out, sep_w, imb = refine_parts(*(lambda w: (w.nbr, w.vwgt, w.part,
                                                w.locked))(_work(seed=9)),
                                   seed=9, k_inst=4)
    assert out.shape == (30,)
    assert set(np.unique(out)) <= {0, 1, 2}
    assert sep_w >= 0.0 and imb >= 0.0
