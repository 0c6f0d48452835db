"""Platform bring-up contracts: the production path each stage picks per
platform, recovery rungs that act only on the failure model's own
exceptions, and where the persistent compile cache lives."""
import os

import jax
import numpy as np
import pytest

from repro.graphs import generators as G
from repro.service import OrderingService, faults


# ------------------------------------------------------------------ #
# `auto` resolves to one production path per platform
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("backend,want", [
    ("tpu", {"fm": "hoisted", "gain": "jnp", "bfs": "jnp", "level": 1}),
    ("cpu", {"fm": "fused", "gain": "jnp", "bfs": "jnp", "level": 0}),
])
def test_auto_paths_per_platform(monkeypatch, backend, want):
    from repro.core.band import bfs_mode_default
    from repro.core.fm import gain_mode_default
    from repro.kernels.ops import fm_mode_default
    from repro.service.router import _fm_base_level
    for var in ("REPRO_FM_MODE", "REPRO_FM_GAIN", "REPRO_BFS_MODE"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    got = {"fm": fm_mode_default(), "gain": gain_mode_default(),
           "bfs": bfs_mode_default(), "level": _fm_base_level()}
    assert got == want


# ------------------------------------------------------------------ #
# no recovery rung swallows a program error
# ------------------------------------------------------------------ #
def test_program_error_in_fm_leaves_drain_undegraded(monkeypatch):
    import repro.service.router as router_mod

    def broken(works, mode=None, **kw):
        raise RuntimeError("compiler refused the kernel")

    monkeypatch.setattr(router_mod, "execute_fm_works", broken)
    svc = OrderingService()
    svc.submit(G.grid2d(12, 12), seed=0, nproc=2)
    with pytest.raises(RuntimeError, match="compiler refused"):
        svc.drain()
    rec = svc._router.recovery
    assert rec.degrade_by_tag == {} and rec.isolations == 0
    st = svc.stats()
    assert st["degraded"] == 0 and st["failed"] == 0


def test_injected_persistent_fault_still_degrades():
    g = G.grid2d(12, 12)
    plan = faults.FaultPlan(seed=0, specs=[
        faults.FaultSpec(site="fm", kind="persistent", at=(0,))])
    with faults.fault_injection(plan):
        svc = OrderingService()
        rid = svc.submit(g, seed=0, nproc=2)
        res = svc.drain()[rid]
    assert res.status == "ok" and res.degraded
    assert np.array_equal(np.sort(res.perm), np.arange(g.n))


# ------------------------------------------------------------------ #
# the persistent compile cache's directory
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    from repro import util
    calls = {}
    monkeypatch.setattr(util, "_CACHE_ON", False)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    util.enable_compile_cache()
    if env_dir:
        # JAX's own reading of the variable stands
        assert "jax_compilation_cache_dir" not in calls
        return
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert calls["jax_compilation_cache_dir"] == util.CACHE_DIR
    assert util.CACHE_DIR == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), \
            ".jax_cache/ is not git-ignored"
