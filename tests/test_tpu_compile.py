"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: each program is lowered and compiled against a v5e:2x2
topology described (not attached) by the installed TPU compiler, which
refuses what the chip's compiler would refuse — block shapes, kernel
bodies, memory.  The centralized executors compile for one chip at real
widths; the three stacked ``shard_map`` collectives compile on a 1- and
a 4-device mesh built from the described devices.

The topology is described inside a module fixture (never at import), and
the whole file stays one file so a single test worker loads the TPU
library.  The persistent compilation cache is off around the compiles: a
TPU entry written here could not be read back without a chip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

L = 8
#: (n, d) ELL widths: 2-D/3-D 7-point meshes, and 27-point meshes
WIDTHS = [(4096, 8), (32768, 32)]
#: (n, d) FM widths of band graphs, their anchors carried as side rows
#: (the 27-point mesh of 13^3), and of tables that unlocked hub rows
#: still widen past ``PULL_K``
FM_WIDTHS = [(2048, 32), (2048, 64), (1024, 32), (2048, 256), (1024, 128)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n,d", WIDTHS)
def test_matching_compiles(one_chip, n, d):
    from repro.core.matching import heavy_edge_matching_multi
    heavy_edge_matching_multi.lower(
        _sds((L, n, d), jnp.int32, one_chip),
        _sds((L, n, d), jnp.int32, one_chip),
        _sds((L, 2), jnp.uint32, one_chip), rounds=8).compile()


@pytest.mark.parametrize("n,d", WIDTHS)
def test_bfs_compiles(one_chip, n, d):
    from repro.core.band import bfs_distance_multi
    bfs_distance_multi.lower(
        _sds((L, n, d), jnp.int32, one_chip),
        _sds((L, n), jnp.int32, one_chip), width=3).compile()


@pytest.mark.parametrize("n,d", WIDTHS + FM_WIDTHS)
def test_fm_hoisted_jnp_compiles(one_chip, n, d):
    from repro.core.fm import SIDE_K, fm_refine_multi
    c = one_chip
    fm_refine_multi.lower(
        _sds((L, n, d), jnp.int32, c), _sds((L, n), jnp.int32, c),
        _sds((L, n), jnp.int8, c), _sds((L, n), jnp.bool_, c),
        _sds((L, 2), jnp.uint32, c), _sds((L,), jnp.float32, c),
        _sds((L,), jnp.int32, c), _sds((L,), jnp.int32, c),
        _sds((L, SIDE_K), jnp.int32, c),
        _sds((L, SIDE_K, n), jnp.float32, c),
        passes=3, pos_only=False, gain_mode="jnp").compile()


# ------------------------------------------------------------------ #
# stacked shard_map collectives on a described mesh
# ------------------------------------------------------------------ #
NLM, DMAX, G = 4096, 8, 512


@pytest.fixture
def parts_mesh(topo, monkeypatch):
    """Point ``make_parts_mesh`` at the described devices; returns a
    ``spec -> NamedSharding`` helper for the chosen part count."""
    from repro.core import dgraph

    def mesh_of(nparts):
        return Mesh(np.array(topo.devices[:nparts]), ("parts",))

    monkeypatch.setattr(dgraph, "make_parts_mesh", mesh_of)
    return lambda nparts: (lambda *spec: NamedSharding(mesh_of(nparts),
                                                       P(*spec)))


@pytest.mark.parametrize("nparts", [1, 4])
def test_halo_stacked_compiles(parts_mesh, nparts):
    from repro.core import dgraph
    sh = parts_mesh(nparts)
    fn = dgraph._halo_stack_jit(nparts, NLM, G, L, "int32")
    fn.lower(_sds((L, nparts, NLM), jnp.int32, sh(None, "parts", None)),
             _sds((L, nparts, G), jnp.int32, sh(None, "parts", None)),
             _sds((L, nparts + 1), jnp.int32, sh(None, None))).compile()


@pytest.mark.parametrize("nparts", [1, 4])
def test_bfs_stacked_compiles(parts_mesh, nparts):
    from repro.core import dgraph
    sh = parts_mesh(nparts)
    fn = dgraph._bfs_stack_jit(nparts, NLM, DMAX, G, 3, L)
    fn.lower(
        _sds((L, nparts, NLM, DMAX), jnp.int32,
             sh(None, "parts", None, None)),
        _sds((L, nparts, NLM), jnp.int32, sh(None, "parts", None)),
        _sds((L, nparts, G), jnp.int32, sh(None, "parts", None)),
        _sds((L, nparts + 1), jnp.int32, sh(None, None))).compile()


@pytest.mark.parametrize("nparts,cap", [(1, 0), (4, 0), (4, 512)])
def test_matching_stacked_compiles(parts_mesh, nparts, cap):
    from repro.core import dgraph
    sh = parts_mesh(nparts)
    fn = dgraph._matching_stack_jit(nparts, NLM, DMAX, G, 8, L, cap)
    fn.lower(
        _sds((L, nparts, NLM, DMAX), jnp.int32,
             sh(None, "parts", None, None)),
        _sds((L, nparts, NLM, DMAX), jnp.int32,
             sh(None, "parts", None, None)),
        _sds((L, nparts, G), jnp.int32, sh(None, "parts", None)),
        _sds((L, nparts + 1), jnp.int32, sh(None, None)),
        _sds((L, nparts), jnp.int32, sh(None, "parts")),
        _sds((L,), jnp.int32, sh(None))).compile()
