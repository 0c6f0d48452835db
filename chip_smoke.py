"""Chip smoke check: drive the ordering service's main path once on a TPU.

    python chip_smoke.py                # one chip (the default phase)
    python chip_smoke.py --four-chips   # the sharded ordering on 4 chips

Run from the root of a checkout; everything happens in this one process.

The default phase orders, through ``OrderingService.submit`` at
``nproc=4``, the paper's audikw1 analog (``SUITE["audikw1-like"]``, a
9,261-vertex 27-point 3D mesh) together with a mixed batch of
1k-3k-vertex graphs (grid2d, circuit, rgg2d) that share buckets in the
router's waves.  A cold run is bound by the TPU compiler, whose time per
executable grows with lanes x padded vertices; the next SUITE size up,
altr4-like (27,000 vertices), does not finish within the 1,200 s the
smoke may take.  It then replays every request (each must come back from the
cache with the identical permutation), and sends one batch graph through
``submit_distributed(distribute(g, 1))``.  A one-part tree goes straight
to the centralized endgame, so the three stacked ``shard_map``
collectives are then run directly on a one-chip mesh and checked against
their host references.  Every result must be a permutation with
status ``ok``, the service must report no failure, shed, degrade or
retry, and the largest batch graph's OPC must stay within 1.05x of host
``nested_dissection`` at the same seed and ``nproc``, run on the CPU
device of the same process.

``--four-chips`` runs only the sharded path: ``submit_distributed`` of
altr4-like over a 4-device mesh, compared with the same graph through
``submit`` on one chip.

The script fails (non-zero exit, no result line) when JAX finds no TPU,
when it runs outside a checkout, or when any check fails.  On success its
last line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
#: the TPU production path of each stage (``fm``, ``gain``, ``bfs``)
TPU_PATHS = {"fm": "hoisted", "gain": "jnp", "bfs": "jnp"}
#: chip OPC over host-reference OPC allowed before the smoke fails
OPC_RATIO_MAX = 1.05
NPROC = 4
SEED = 0


class SmokeFailure(RuntimeError):
    """One check of the smoke did not hold."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def is_permutation(perm, n: int) -> bool:
    import numpy as np
    p = np.asarray(perm)
    return (p.shape == (n,) and np.issubdtype(p.dtype, np.integer)
            and np.array_equal(np.sort(p), np.arange(n)))


def selected_paths() -> dict:
    from repro.core.band import bfs_mode_default
    from repro.core.fm import gain_mode_default
    from repro.kernels.ops import fm_mode_default
    return {"fm": fm_mode_default(), "gain": gain_mode_default(),
            "bfs": bfs_mode_default()}


def default_graphs() -> tuple:
    """(audikw1-like, the mixed batch) of the default phase."""
    from repro.graphs import generators as G
    batch = {"grid2d-40": G.grid2d(40, 40),
             "circuit-3000": G.circuit(3000, seed=1),
             "rgg2d-2000": G.rgg2d(2000, seed=2),
             "grid2d-32": G.grid2d(32, 32)}
    return ("audikw1-like", G.SUITE["audikw1-like"]()), batch


def four_chip_graph() -> tuple:
    from repro.graphs import generators as G
    return "altr4-like", G.SUITE["altr4-like"]()


class _CompileCounter:
    """Event-bus collector: dispatches billed as a first use (a new
    executable, built or loaded from the persistent cache)."""

    def __init__(self):
        self.compiles = 0

    def on_event(self, kind: str, payload: dict) -> None:
        if kind == "stage" and payload.get("compile"):
            self.compiles += 1


def _report_stages(ins, counter, log_fn) -> None:
    from repro.core import dgraph
    for name, d in sorted(ins.stage_detail.items()):
        log_fn(f"stage {name}: compile {d['compile_s']:.3f}s "
               f"dispatch {d['dispatch_s']:.3f}s")
    log_fn(f"executables: {counter.compiles} first-use dispatches, "
           f"{dgraph.jit_cache_size()} stacked-collective executables "
           f"cached, {counter.compiles - dgraph.jit_cache_size()} "
           "centralized buckets")


def run_collectives(g, nparts: int = 1, log_fn=log) -> None:
    """The three stacked ``shard_map`` collectives, two lanes each, on an
    ``nparts`` mesh, checked against the centralized references.

    ``submit_distributed`` at P=1 hands the whole tree to the
    centralized endgame, so this is what runs the collectives on one
    chip.
    """
    import numpy as np
    from repro.core import dgraph
    from repro.core.band import bfs_distance
    from repro.core.matching import validate_matching

    dg = dgraph.distribute(g, nparts)
    rng = np.random.default_rng(SEED)
    xs = [rng.integers(0, 1000, (nparts, dg.n_loc_max)).astype(np.int32)
          for _ in range(2)]
    got = dgraph.halo_exchange_stacked([dg, dg], xs)
    for x, h in zip(xs, got):
        check(np.array_equal(h, dgraph.halo_reference(dg, x)),
              "halo_exchange_stacked differs from halo_reference")

    width = 4
    nbr, _ = g.to_ell()
    srcs = [np.arange(g.n) == v0 for v0 in (0, g.n // 2)]
    got = dgraph.distributed_bfs_stacked(
        [dg, dg], [dgraph.shard_vector(dg, s) for s in srcs], width)
    for d, src in zip(got, srcs):
        want = np.asarray(bfs_distance(nbr, src, width))
        flat = dgraph.unshard_vector(dg, d)
        check(np.array_equal(np.minimum(flat, width + 1),
                             np.minimum(want, width + 1)),
              "distributed_bfs_stacked differs from bfs_distance")

    got = dgraph.distributed_matching_stacked([dg, dg], [0, 5])
    for m_sh in got:
        m = dgraph.unshard_vector(dg, m_sh)
        check(validate_matching(m), "distributed matching is invalid")
        mated = np.flatnonzero(m != np.arange(g.n))
        check(all(int(m[a]) in g.neighbors(a) for a in mated),
              "distributed matching pairs non-neighbours")
    log_fn(f"collectives P={nparts}: halo, bfs and matching agree with "
           "their host references")


@contextlib.contextmanager
def _phase(name: str, phase_s: dict, log_fn):
    """Time one phase and log it as soon as it ends."""
    t0 = time.perf_counter()
    yield
    phase_s[name] = time.perf_counter() - t0
    log_fn(f"phase {name}: {phase_s[name]:.3f}s")


def _check_ok(label: str, res, n: int) -> None:
    check(res is not None and res.status == "ok",
          f"{label}: status {getattr(res, 'status', None)}")
    check(is_permutation(res.perm, n), f"{label}: result is not a "
          "permutation")


def run_default(big, batch: dict, nproc: int = NPROC, seed: int = SEED,
                log_fn=log) -> dict:
    """The default phase on the process's default device; returns a
    summary dict and raises ``SmokeFailure`` on a failed check."""
    import jax
    import numpy as np
    from repro import obs
    from repro.core import dgraph
    from repro.core.nd import nested_dissection
    from repro.service import OrderingService
    from repro.sparse.symbolic import nnz_opc

    graphs = {big[0]: big[1], **batch}
    for name, g in graphs.items():
        log_fn(f"graph {name}: n={g.n} m={g.m}")
    dist_name = next(iter(batch))
    g_dist = batch[dist_name]
    svc = OrderingService()
    counter = _CompileCounter()
    phase_s = {}
    obs.register_collector(counter)
    try:
        with dgraph.instrument() as ins:
            with _phase("service", phase_s, log_fn):
                rids = {name: svc.submit(g, seed=seed, nproc=nproc)
                        for name, g in graphs.items()}
                svc.drain()
            perms = {}
            for name, rid in rids.items():
                res = svc.poll(rid)
                _check_ok(name, res, graphs[name].n)
                perms[name] = res.perm
            with _phase("replay", phase_s, log_fn):
                for name, g in graphs.items():
                    res = svc.poll(svc.submit(g, seed=seed, nproc=nproc))
                    check(res is not None and res.cached,
                          f"{name}: replay was not a cache hit")
                    check(np.array_equal(res.perm, perms[name]),
                          f"{name}: replayed permutation differs")
            with _phase("distributed_p1", phase_s, log_fn):
                rid = svc.submit_distributed(dgraph.distribute(g_dist, 1),
                                             seed=seed)
                svc.drain()
            res = svc.poll(rid)
            _check_ok(f"{dist_name} (P=1)", res, g_dist.n)
            dist_perm = res.perm
            with _phase("collectives_p1", phase_s, log_fn):
                run_collectives(g_dist, 1, log_fn)
    finally:
        obs.unregister_collector(counter)

    st = svc.stats()
    log_fn("service: " + json.dumps(
        {k: st[k] for k in ("requests", "computed", "cache_hits", "failed",
                            "shed", "degraded", "fault_retries")}))
    for k in ("failed", "shed", "degraded", "fault_retries"):
        check(st[k] == 0, f"service stats: {k} = {st[k]}")
    _report_stages(ins, counter, log_fn)

    opc = {name: float(nnz_opc(graphs[name], p)[1])
           for name, p in perms.items()}
    for name, v in opc.items():
        log_fn(f"opc {name}: {v:.6e}")
    log_fn(f"opc {dist_name} (distributed P=1): "
           f"{float(nnz_opc(g_dist, dist_perm)[1]):.6e}")

    ref_name = max(batch, key=lambda k: batch[k].n)
    with _phase("cpu_reference", phase_s, log_fn):
        with jax.default_device(jax.devices("cpu")[0]):
            ref = nested_dissection(batch[ref_name], seed=seed,
                                    nproc=nproc)
    opc_ref = float(nnz_opc(batch[ref_name], ref)[1])
    ratio = opc[ref_name] / opc_ref
    log_fn(f"reference {ref_name}: opc ratio {ratio:.6f} "
           f"(device {opc[ref_name]:.6e} / cpu {opc_ref:.6e}), "
           f"bit-identical {bool(np.array_equal(ref, perms[ref_name]))}")
    check(ratio <= OPC_RATIO_MAX,
          f"{ref_name}: OPC ratio {ratio:.4f} > {OPC_RATIO_MAX}")
    return {"phase_s": phase_s, "opc": opc, "opc_ratio": ratio,
            "stats": st}


def run_four_chips(g_name: str, g, nparts: int = 4, nproc: int = NPROC,
                   seed: int = SEED, log_fn=log) -> dict:
    """The sharded ordering over ``nparts`` devices, compared with the
    host-tree path on one device of the same process."""
    from repro.core import dgraph
    from repro.service import OrderingService
    from repro.sparse.symbolic import nnz_opc

    log_fn(f"graph {g_name}: n={g.n} m={g.m}")
    svc = OrderingService()
    phase_s, opc = {}, {}
    with _phase(f"distributed_p{nparts}", phase_s, log_fn):
        rid_d = svc.submit_distributed(dgraph.distribute(g, nparts),
                                       seed=seed)
        svc.drain()
    with _phase("one_chip", phase_s, log_fn):
        rid_h = svc.submit(g, seed=seed, nproc=nproc)
        svc.drain()
    for label, rid in (("distributed", rid_d), ("one_chip", rid_h)):
        res = svc.poll(rid)
        _check_ok(label, res, g.n)
        opc[label] = float(nnz_opc(g, res.perm)[1])
        log_fn(f"opc {label}: {opc[label]:.6e}")
    st = svc.stats()
    for k in ("failed", "shed", "degraded", "fault_retries"):
        check(st[k] == 0, f"service stats: {k} = {st[k]}")
    ratio = opc["distributed"] / opc["one_chip"]
    log_fn(f"opc ratio distributed/one_chip: {ratio:.6f}")
    check(ratio <= OPC_RATIO_MAX,
          f"OPC ratio {ratio:.4f} > {OPC_RATIO_MAX}")
    return {"phase_s": phase_s, "opc_ratio": ratio}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded ordering on 4 chips")
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run from a "
              "checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    # the OPC reference runs on the CPU device of this process, so keep
    # the CPU backend in a platform list that names the accelerator
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devs)}",
              file=sys.stderr)
        return 1

    from repro.util import enable_compile_cache
    enable_compile_cache()
    t_start = time.perf_counter()
    try:
        paths = selected_paths()
        log("paths: " + json.dumps(paths))
        check(paths == TPU_PATHS,
              f"selected paths {paths} are not the TPU paths {TPU_PATHS}")
        if args.four_chips:
            run_four_chips(*four_chip_graph())
        else:
            run_default(*default_graphs())
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    log(f"total: {time.perf_counter() - t_start:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": need}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
