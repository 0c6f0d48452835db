"""Distributed nested dissection: OPC parity vs the host driver and
wall-clock across virtual device counts.

With ``JAX_PLATFORMS=cpu`` it needs 8 virtual host devices: unless
``XLA_FLAGS`` already asks for them it re-execs itself with
``--xla_force_host_platform_device_count=8`` (the flag must be set before
jax initializes; the decision is read from the environment, so the child
never needs a chip the parent holds).  On any other platform it runs in
this process on the devices present, sweeping the counts of
``DEVICE_COUNTS`` that fit.  Emits ``BENCH_dnd.json``:

  * per-graph OPC of ``distributed_nested_dissection`` on 8 shards vs host
    ``nested_dissection`` at nproc=8 (same seed) — the mean ratio is
    asserted ≤ 1.03 (the tracked quality-parity bound, tightened from
    1.05 with the alternating-color band schedule);
  * wall-clock of the distributed driver on 1 / 2 / 4 / 8 virtual devices
    (CPU shard_map collectives: this tracks dispatch overhead trends, not
    real-accelerator speedup), plus ``p8_over_p1`` — the ratio the
    frontier driver is accountable for (launch latency used to grow with
    tree width; lane-stacking caps per-wave launches at the bucket
    count, asserted ≤ the bound the CI spmd job also re-checks).  The
    two ratio endpoints are min-of-3 timings with the first sample
    discarded as warmup — virtual devices oversubscribe small CPU
    runners and the cold sample carries compile/cache-load, so
    min-of-2 still swung ~1.7x; ``timing_jitter`` (and
    ``timing_jitter_fm`` for the gated FM stage) track the residual
    post-warmup swing;
  * ``launches_by_level`` (per graph): the frontier driver's per-wave
    outstanding works / shape buckets / collective launches by kind,
    with ``launch_budget_ok`` asserting launches == buckets on every
    wave — O(buckets × rounds) per level, not O(siblings × rounds);
  * ``stage_s``: per-stage wall-clock of the p=8 runs (match / bfs /
    halo / band-FM / rebuild / endgame) from ``dgraph.instrument()``;
  * ``match_gather_words``: total all_gather words of the matching
    launches — 3 buffers per round since the grant gather-back
    compaction (was 4), with the proposal buffers gathered at the
    lossless proposer cap when the compact path pays for itself;
    ``match_gather_words_dense`` books the counterfactual dense cost,
    so the compaction win is the gap between the two;
  * ``router``: the unified-router multi-request section — N=3
    concurrent distributed orderings drained through ONE shared
    ``WaveRouter`` vs 3 sequential single-request drains:
    ``router_launches_per_wave`` (mean launches per shared wave),
    ``cross_request_share_rate`` (launches that served lanes of ≥ 2
    requests), and the gated claims that the concurrent drain is
    bit-identical to the sequential drains while issuing strictly fewer
    collective launches;
  * ``max_gather``: the largest centralizing gather (``to_host`` /
    ``unshard_vector`` element count) observed during the p=8 runs —
    the gather-free pipeline keeps it bounded by the configured
    thresholds, independent of graph size;
  * ``band``: a forced-sharded-band run of the first workload graph
    (``band_central_threshold`` lowered so the §3.3 sharded path really
    executes) reporting the band-path OPC ratio and the per-round
    conflict / repair-kick / ghost-pull counts of every sharded band
    refinement — the alternating-color schedule (the default) is
    asserted conflict-free.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

DEVICE_COUNTS = (1, 2, 4, 8)
_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def _reexec_with_devices() -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" {_DEVICE_FLAG}=8").strip()
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-m", "benchmarks.dnd_bench"],
                         env=env)
    if res.returncode:
        raise SystemExit(res.returncode)


def workload():
    from benchmarks.common import quick
    from repro.graphs import generators as G
    if quick():
        return {"grid2d-24": G.grid2d(24, 24),
                "grid3d-9": G.grid3d(9, 9, 9)}
    return {"grid2d-48": G.grid2d(48, 48),
            "grid3d-12": G.grid3d(12, 12, 12),
            "rgg2d-3000": G.rgg2d(3000, seed=2)}


def main() -> None:
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and _DEVICE_FLAG not in os.environ.get("XLA_FLAGS", "")):
        _reexec_with_devices()
        return
    # REPRO_TRACE_OUT=path captures a span trace of the whole bench run
    # (the re-exec subprocess inherits the env, so the child writes the
    # file); the root ``bench`` span covers the full session, which is
    # what makes scripts/trace_summary.py report >= 95% coverage
    trace_out = os.environ.get("REPRO_TRACE_OUT")
    if not trace_out:
        _bench()
        return
    from repro import obs
    with obs.tracing() as tracer:
        with tracer.span("bench", bench="dnd"):
            _bench()
    tracer.export_chrome(trace_out)
    print(f"trace written to {trace_out} ({len(tracer.spans)} spans)")


def _bench() -> None:
    import numpy as np
    from benchmarks.common import row
    from repro.core.dgraph import distribute, instrument, jit_cache_size
    from repro.core.dnd import (DNDConfig, distributed_nested_dissection,
                                distributed_order_batch, track_band_stats)
    from repro.core.nd import nested_dissection
    from repro.sparse.symbolic import nnz_opc
    from repro.util import enable_compile_cache
    enable_compile_cache()
    import jax
    dev = jax.devices()
    counts = tuple(p for p in DEVICE_COUNTS if p <= len(dev))

    graphs = workload()
    per_graph = {}
    wall = {p: 0.0 for p in counts}
    ratios = []
    max_gather = 0
    stage_s = {}
    stage_detail = {}
    match_words = 0
    match_words_dense = 0
    budget_ok = True
    timing_jitter = 1.0
    timing_jitter_fm = 1.0
    for name, g in graphs.items():
        perm_h = nested_dissection(g, seed=0, nproc=8)
        opc_h = nnz_opc(g, perm_h)[1]
        entry = {"n": g.n, "opc_host": opc_h}
        for p in counts:
            dg = distribute(g, p)
            # the endpoints of the gated p8/p1 ratio are timed as the
            # min of THREE runs with the first discarded as warmup:
            # virtual host devices oversubscribe small CPU runners, so
            # min-of-2 endpoint samples still swung ~1.7x run-to-run
            # (the first sample carries compile / cache-load, e.g.
            # grid2d-24 t_p8 10.8 vs 2.4).  The steady-state reps
            # measure the dispatch cost the frontier claim is about
            reps = 3 if p in (min(counts), max(counts)) else 1
            samples = []
            fm_rep_s = []
            for rep in range(reps):
                t0 = time.perf_counter()
                with instrument() as ins_rep:
                    perm_d = distributed_nested_dissection(dg, seed=0)
                samples.append(time.perf_counter() - t0)
                fm_rep_s.append(ins_rep.stage_s.get("fm", 0.0))
                if rep == 0:
                    ins = ins_rep
            steady = samples[1:] if len(samples) > 2 else samples
            dt = min(steady)
            wall[p] += dt
            entry[f"t_p{p}_s"] = round(dt, 3)
            # raw samples stay in the artifact so the gated p8/p1 ratio
            # is debuggable when a CI runner swings; timing_jitter is
            # the worst max/min swing over the post-warmup endpoint
            # samples (the warmup sample is recorded but not gated on)
            entry[f"t_p{p}_samples"] = [round(s, 3) for s in samples]
            if len(steady) > 1:
                timing_jitter = max(timing_jitter,
                                    max(steady) / max(min(steady), 1e-9))
            # FM-section jitter, tracked separately: the fm stage gate
            # below compares against a wall-clock baseline, so its own
            # run-to-run swing must be visible in the artifact
            if p == max(counts) and len(fm_rep_s) > 2:
                fm_steady = fm_rep_s[1:]
                timing_jitter_fm = max(
                    timing_jitter_fm,
                    max(fm_steady) / max(min(fm_steady), 1e-9))
            if p == max(counts):
                opc_d = nnz_opc(g, perm_d)[1]
                entry["opc_dnd"] = opc_d
                entry["opc_ratio"] = round(opc_d / opc_h, 4)
                ratios.append(opc_d / opc_h)
                entry["max_gather"] = max(s for _, s in ins.gathers)
                max_gather = max(max_gather, entry["max_gather"])
                # frontier wave accounting: works vs buckets vs launches
                entry["launches_by_level"] = ins.waves
                entry["launch_budget_ok"] = all(
                    w["launches"][k] == w["buckets"][k] <= w["works"][k]
                    for w in ins.waves for k in w["launches"])
                budget_ok &= entry["launch_budget_ok"]
                for k, v in ins.stage_s.items():
                    stage_s[k] = stage_s.get(k, 0.0) + v
                for k, d in ins.stage_detail.items():
                    sd = stage_detail.setdefault(
                        k, {"compile_s": 0.0, "dispatch_s": 0.0})
                    sd["compile_s"] += d["compile_s"]
                    sd["dispatch_s"] += d["dispatch_s"]
                match_words += sum(l["words"] for l in ins.launches
                                   if l["kind"] == "dmatch")
                match_words_dense += sum(
                    l["words_dense"] for l in ins.launches
                    if l["kind"] == "dmatch")
        per_graph[name] = entry
        row(f"dnd/{name}", entry[f"t_p{max(counts)}_s"] * 1e6,
            n=g.n, opc_ratio=entry["opc_ratio"],
            max_gather=entry["max_gather"],
            budget_ok=entry["launch_budget_ok"],
            **{f"t_p{p}": entry[f"t_p{p}_s"] for p in counts})

    # unified-router multi-request drain: N=3 concurrent distributed
    # orderings through ONE shared WaveRouter vs 3 sequential drains —
    # same permutations, strictly fewer collective launches (the wave
    # router's reason to exist)
    p_hi0 = max(counts)
    r_items = (list(graphs.items()) * 3)[:3]
    r_seeds = [11, 23, 37]
    r_dgs = [distribute(g, p_hi0) for _, g in r_items]
    with instrument() as ins_rseq:
        seq_perms = [distributed_nested_dissection(d, seed=s)
                     for d, s in zip(r_dgs, r_seeds)]
    t0 = time.perf_counter()
    with instrument() as ins_rcon:
        con_perms = distributed_order_batch(r_dgs, r_seeds)
    router_dt = time.perf_counter() - t0

    def _dist_launches(ins):
        return sum(1 for l in ins.launches
                   if l["kind"] in ("dhalo", "dbfs", "dmatch"))

    r_waves = ins_rcon.waves
    r_total_launches = sum(sum(w["launches"].values()) for w in r_waves)
    r_shared = sum(w.get("shared_launches", 0) for w in r_waves)
    router = {
        "requests": len(r_dgs),
        "graphs": [name for name, _ in r_items],
        "bit_identical": bool(all(
            np.array_equal(a, b)
            for a, b in zip(seq_perms, con_perms))),
        "launches_concurrent": _dist_launches(ins_rcon),
        "launches_sequential": _dist_launches(ins_rseq),
        "waves": len(r_waves),
        "router_launches_per_wave": round(
            r_total_launches / max(len(r_waves), 1), 3),
        "cross_request_share_rate": round(
            r_shared / max(r_total_launches, 1), 4),
        "multi_request_waves": sum(
            1 for w in r_waves if w.get("requests", 1) >= 2),
        "t_s": round(router_dt, 3),
        "jit_cache_size": jit_cache_size(),
    }
    row("dnd/router", router_dt * 1e6,
        launches_concurrent=router["launches_concurrent"],
        launches_sequential=router["launches_sequential"],
        share_rate=router["cross_request_share_rate"],
        per_wave=router["router_launches_per_wave"])

    # forced-sharded-band run (§3.3 alternating-color schedule): lower
    # the centralization threshold so bands really refine sharded, and
    # report the schedule's per-round conflict accounting + band OPC
    band_name, band_g = next(iter(graphs.items()))
    band_cfg = DNDConfig(centralize_threshold=256,
                         band_central_threshold=128)
    dg = distribute(band_g, max(counts))
    t0 = time.perf_counter()
    with track_band_stats() as bstats:
        perm_b = distributed_nested_dissection(dg, seed=0, cfg=band_cfg)
    band_dt = time.perf_counter() - t0
    opc_b = nnz_opc(band_g, perm_b)[1]
    conflicts_by_round = [s["conflicts"] for s in bstats]
    band = {
        "graph": band_name,
        "opc_ratio": round(opc_b / per_graph[band_name]["opc_host"], 4),
        "t_s": round(band_dt, 3),
        "band_refines": len(bstats),
        "conflicts_by_round": conflicts_by_round,
        "conflict_total": int(sum(sum(c) for c in conflicts_by_round)),
        "repair_kicks": int(sum(sum(s["repairs"]) for s in bstats)),
        "ghost_pulls": int(sum(sum(s["pulls"]) for s in bstats)),
    }
    row(f"dnd/band/{band_name}", band_dt * 1e6,
        opc_ratio=band["opc_ratio"], conflicts=band["conflict_total"],
        kicks=band["repair_kicks"], pulls=band["ghost_pulls"])

    ratio_mean = float(np.mean(ratios))
    p_lo, p_hi = min(counts), max(counts)
    p8_over_p1 = wall[p_hi] / wall[p_lo] if wall[p_lo] else 0.0
    out = {
        "device": {"platform": dev[0].platform, "kind": dev[0].device_kind,
                   "count": len(dev)},
        "graphs": per_graph,
        "wallclock_s": {str(p): round(wall[p], 3) for p in counts},
        "p8_over_p1": round(p8_over_p1, 3),
        "timing_jitter": round(timing_jitter, 3),
        "timing_jitter_fm": round(timing_jitter_fm, 3),
        # every stage decomposed into first-call compile (trace + lower
        # + XLA compile or persistent-cache load) vs steady-state
        # dispatch, split by jit-cache-key first use (DESIGN.md §6);
        # per-wave rollups (t_s + stage_s per frontier wave) live in
        # graphs.*.launches_by_level
        "stage_s": {k: {"total_s": round(v, 3),
                        "compile_s": round(stage_detail.get(
                            k, {}).get("compile_s", 0.0), 3),
                        "dispatch_s": round(stage_detail.get(
                            k, {}).get("dispatch_s", 0.0), 3)}
                    for k, v in sorted(stage_s.items())},
        "launch_budget_ok": budget_ok,
        "match_gather_words": match_words,
        "match_gather_words_dense": match_words_dense,
        "opc_ratio_mean": round(ratio_mean, 4),
        "max_gather": max_gather,
        "router": router,
        "band": band,
    }
    with open("BENCH_dnd.json", "w") as f:
        json.dump(out, f, indent=2)
    row("dnd/opc_ratio_mean", 0.0, ratio=round(ratio_mean, 4))
    row("dnd/wallclock", wall[p_hi] * 1e6, p8_over_p1=round(p8_over_p1, 3),
        **{f"stage_{k}": round(v, 2) for k, v in sorted(stage_s.items())})
    # asserts run after the dump so a failing bound still leaves the
    # artifact around for debugging
    assert budget_ok, \
        "frontier wave launched more collectives than shape buckets"
    # lane-stacking caps per-wave launches at the bucket count, so the
    # wall-clock must stop growing with virtual device count the way the
    # depth-first driver's did (pre-frontier baseline: 3.03x).  The
    # fused FM pass loop re-based this ratio: it removed most of the
    # p=1 wall (18.8s -> 3.5s steady across the workload) while the
    # p=8 endpoint stays dominated by shard_map collective overhead on
    # oversubscribed virtual devices, so the same absolute overhead now
    # divides a much smaller denominator (measured 6.2x here vs 1.9x
    # pre-fusion — p=8 absolute wall IMPROVED 36.1s -> 21.9s).  The
    # structural per-sibling-launch regression is asserted directly by
    # the launch-budget checks above; this bound (measured 6.2x, jitter
    # <= 1.3x) only catches wholesale launch-growth blowups
    # the two wall-clock gates below are calibrated on the 8-virtual-
    # device CPU runner and say nothing about another platform
    on_cpu = dev[0].platform == "cpu"
    assert not on_cpu or p8_over_p1 <= 7.5, (
        f"p=8 wall-clock is {p8_over_p1:.2f}x p=1 — frontier batching "
        "regressed toward per-sibling launch growth "
        "(post-fusion baseline 6.2x)")
    # the router acceptance gates: concurrent == sequential bit-for-bit,
    # with strictly fewer collective launches and real cross-request
    # sharing
    assert router["bit_identical"], \
        "shared-router drain differs from sequential single drains"
    assert (router["launches_concurrent"]
            < router["launches_sequential"]), (
        f"concurrent drain launched {router['launches_concurrent']}x, "
        f"sequential {router['launches_sequential']}x — no sharing")
    assert router["cross_request_share_rate"] > 0.0, \
        "no launch ever served lanes from >= 2 requests"
    assert band["band_refines"] > 0, "no sharded band refinement ran"
    assert band["conflict_total"] == 0 and band["repair_kicks"] == 0, (
        "alternating-color schedule reported conflicts: "
        f"{band['conflicts_by_round']}")
    assert ratio_mean <= 1.03, (
        f"distributed ND mean OPC ratio {ratio_mean:.3f} > 1.03 vs host")
    # the fused-FM acceptance gate: the on-device pass loop (plus the
    # bucket merge from dropping the max_moves sub-bucket) must at
    # least halve the p=8 FM stage versus the pre-fusion baseline.
    # 69.334 is the committed stage_s.fm.total_s of the PR 7 artifact
    # (cold rep: compile 31.571 + dispatch 37.763 on the same
    # 8-virtual-device CPU runner class this bench targets)
    fm_total = stage_s.get("fm", 0.0)
    assert not on_cpu or fm_total <= 0.55 * 69.334, (
        f"stage_s.fm {fm_total:.1f}s > 0.55x the 69.334s pre-fusion "
        "baseline — the fused FM pass loop regressed")


if __name__ == "__main__":
    main()
