"""Parallel ordering scaling demo + the distributed data structure at work.

    PYTHONPATH=src python examples/order_mesh.py

Part 1 sweeps the simulated process count and shows the paper's headline
result: PT-Scotch ordering quality is stable (or improves) with p while the
ParMETIS-like baseline degrades.  Part 2 runs the halo-exchange/BFS data
plane over an 8-way shard_map mesh: with ``JAX_PLATFORMS=cpu`` on 8
virtual host devices, elsewhere on the devices the platform has.
"""
import os
if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.baselines import parmetis_like, pt_scotch_like
from repro.core.dgraph import distribute, distributed_bfs
from repro.graphs.generators import grid3d
from repro.sparse.symbolic import nnz_opc
from repro.util import enable_compile_cache


def main():
    enable_compile_cache()
    g = grid3d(10, 10, 10)
    print(f"graph: |V|={g.n} |E|={g.m}")
    print(f"{'p':>4} {'O_PTS':>12} {'O_PM':>12} {'PM/PTS':>7}")
    o_ref = None
    for p in (2, 8, 32):
        o_pts = nnz_opc(g, pt_scotch_like(g, seed=0, nproc=p))[1]
        o_pm = nnz_opc(g, parmetis_like(g, seed=0, nproc=p))[1]
        if p == 8:
            o_ref = o_pts
        print(f"{p:>4} {o_pts:>12.3e} {o_pm:>12.3e} {o_pm/o_pts:>7.2f}")

    print("\ndistributed band-BFS over 8 shards (halo exchange/shard_map):")
    dg = distribute(g, 8)
    src = np.zeros((8, dg.n_loc_max), bool)
    src[0, 0] = True
    t0 = time.time()
    dist = distributed_bfs(dg, src, width=3)
    n_band = int((dist <= 3).sum())
    print(f"  band(width=3) holds {n_band} vertices "
          f"({time.time()-t0:.2f}s, {dg.nparts} shards, "
          f"ghosts/shard max {int(dg.n_ghost.max())})")

    print("\nend-to-end distributed nested dissection (8 shards):")
    from repro.core.dnd import distributed_nested_dissection
    t0 = time.time()
    perm = distributed_nested_dissection(dg, seed=0)
    opc = nnz_opc(g, perm)[1]
    print(f"  OPC {opc:.3e} in {time.time()-t0:.1f}s "
          f"(host nproc=8 reference above: {o_ref:.3e})")


if __name__ == "__main__":
    main()
