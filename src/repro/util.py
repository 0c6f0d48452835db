"""Shared utilities: persistent compile cache, pow2 bucketing, timers."""
from __future__ import annotations

import os
import time

_CACHE_ON = False

#: the persistent compile cache's home when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: one fixed directory in the checkout (git-ignored), so every
#: process of a run, and every later run, hits the same entries
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> None:
    """Persistent XLA compilation cache (huge win for the host-recursion
    control plane, which reuses a small family of jitted kernels).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it
    stands and no directory is set here; otherwise the cache lives in
    ``CACHE_DIR``.
    """
    global _CACHE_ON
    if _CACHE_ON:
        return
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    _CACHE_ON = True


_MASK64 = (1 << 64) - 1


def mix_seeds(*vals: int) -> int:
    """Splitmix64-style hash of a seed path → 31-bit PRNG seed.

    Per-node seeds in the ND tree are derived by chaining this over
    (seed, node path, level).  Affine formulas like ``seed * 31`` or
    ``seed * 101 + lvl`` collapse at ``seed=0`` (every node at a level
    reuses the identical noise stream); a full-avalanche mix does not.
    """
    h = 0
    for v in vals:
        h = (h + int(v) + 0x9E3779B97F4A7C15) & _MASK64
        h ^= h >> 30
        h = (h * 0xBF58476D1CE4E5B9) & _MASK64
        h ^= h >> 27
        h = (h * 0x94D049BB133111EB) & _MASK64
        h ^= h >> 31
    return h & 0x7FFFFFFF


def pow2(x: int, lo: int = 64) -> int:
    v = lo
    while v < x:
        v *= 2
    return v


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.dt = time.perf_counter() - self.t0
