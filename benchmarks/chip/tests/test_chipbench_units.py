"""The chip benchmark's own arithmetic, on the CPU: graph pools, the
plain reference's fill, the end-to-end reductions, the trace reduction
and the per-layer readers."""
import glob
import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, CHIP)

import devtrace  # noqa: E402
import kernel_bytes  # noqa: E402
import loop  # noqa: E402
import pool  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402

MESH = {"generator": "grid3d", "nx": 6, "ny": 6, "nz": 6, "stencil": 27}
CIRCUIT = {"generator": "circuit", "n_min": 200, "n_max": 400,
           "fanout": 2.4}


def _run_module():
    s = importlib.util.spec_from_file_location(
        "chipbench_run_units", os.path.join(CHIP, "run.py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cfg", [MESH, CIRCUIT])
def test_pool_same_for_same_seed_and_never_repeats(cfg):
    cfg = dict(cfg, set_seed=77, set_size=30, set_block=4)
    seed = 2**31 + 12345                  # larger than 32 signed bits
    a = pool.build_pool(cfg, seed)
    b = pool.build_pool(cfg, seed)
    assert [r.fingerprint for r in a] == [r.fingerprint for r in b]
    assert all(np.array_equal(x.edges, y.edges) and x.seed == y.seed
               for x, y in zip(a, b))
    assert len({r.fingerprint for r in a}) == 30
    assert [r.index for r in a] == list(range(30))
    # another seed: the same graphs in each block, in another order
    c = pool.build_pool(cfg, seed + 1)
    for b in range(0, 30, 4):
        assert {r.fingerprint for r in a[b:b + 4]} == \
            {r.fingerprint for r in c[b:b + 4]}
    assert [r.fingerprint for r in a] != [r.fingerprint for r in c]
    # a larger set starts with the smaller one's
    first = [r.fingerprint for r in pool.build_set(cfg, 77, 10)]
    assert [r.fingerprint for r in pool.build_set(cfg, 77, 30)][:10] == first


def test_pool_draws_again_on_a_repeated_graph(monkeypatch):
    graphs = iter([pool.grid3d(3, 3, 3), pool.grid3d(3, 3, 3),
                   pool.grid3d(4, 3, 3)])
    monkeypatch.setitem(pool.GENERATORS, "fixed",
                        lambda cfg, rng, base: next(graphs))
    got = pool.build_set({"generator": "fixed"}, 0, 2)
    assert [r.n for r in got] == [27, 36]


def test_fingerprint_ignores_edge_order_and_direction():
    n, e = pool.circuit(300, seed=4)
    flipped = e[::-1, ::-1]
    assert pool.fingerprint(n, e) == pool.fingerprint(n, flipped)


@pytest.mark.parametrize("cfg", [MESH, CIRCUIT])
def test_reference_fill_matches_the_program_and_the_dense_oracle(cfg):
    from repro.core.graph import Graph
    from repro.sparse.symbolic import dense_fill_oracle, nnz_opc
    req = pool.build_set(cfg, 3, 1)[0]
    xadj, adjncy = reference.csr(req.n, req.edges)
    g = Graph.from_edges(req.n, req.edges)
    rng = np.random.default_rng(0)
    for perm in (rng.permutation(req.n),
                 reference.nested_dissection(xadj, adjncy)):
        assert reference.is_permutation(perm, req.n)
        want = nnz_opc(g, perm)[1]
        assert reference.opc(xadj, adjncy, perm) == pytest.approx(want)
    small = pool.build_set(dict(cfg, nx=4, ny=4, nz=4, n_min=60, n_max=60),
                           3, 1)[0]
    xs, adj_s = reference.csr(small.n, small.edges)
    perm = reference.nested_dissection(xs, adj_s, leaf_size=8)
    assert reference.opc(xs, adj_s, perm) == pytest.approx(
        dense_fill_oracle(Graph.from_edges(small.n, small.edges), perm)[1])


def test_top_imbalance_reads_the_top_separator_from_the_tree():
    # a path 0-1-...-6
    n, e = 7, np.stack([np.arange(6), np.arange(1, 7)], 1)
    xadj, adjncy = reference.csr(n, e)
    # separator {3} last, parts {0,1,2} and {4,5,6}: balanced
    assert reference.top_imbalance(xadj, adjncy,
                                   [0, 1, 2, 4, 5, 6, 3]) == 0.0
    # separator {1} last, parts {0} and {2..6}
    assert reference.top_imbalance(xadj, adjncy, [0, 2, 3, 4, 5, 6, 1]) \
        == pytest.approx(4 / 7)
    # the reference's own dissection of a mesh is near balance; the
    # reference in bfloat16, whose sums of unit weights stop at 256, is not
    req = pool.build_set(dict(MESH, nx=9, ny=9, nz=9), 5, 1)[0]
    import check
    assert check.readings(req.n, req.edges, reference.nested_dissection(
        *reference.csr(req.n, req.edges)))["top_imbalance"] < 0.12
    assert check.readings(req.n, req.edges, None, "bfloat16")[
        "top_imbalance"] > 0.5


@pytest.mark.parametrize("perm,want", [
    ([0, 1, 2, 4, 5, 6, 3], 0.0),         # separator {3} last: balanced
    ([0, 2, 3, 4, 5, 6, 1], 4 / 7),       # separator {1} last
])
def test_split_imbalance_reads_the_path_cases_as_the_tree_does(perm, want):
    xadj, adjncy = reference.csr(7, np.stack([np.arange(6),
                                              np.arange(1, 7)], 1))
    assert reference.split_imbalance(xadj, adjncy, perm) == \
        reference.top_imbalance(xadj, adjncy, perm) == pytest.approx(want)


def _two_paths(k, extra):
    """Parts 0..k-1 and k..2k-1, two paths, then the vertices ``extra``
    adds: edges ``(n, 2)`` among ids from 2k on and the path ends."""
    a, b = np.arange(k), np.arange(k, 2 * k)
    e = np.concatenate([np.stack([a[:-1], a[1:]], 1),
                        np.stack([b[:-1], b[1:]], 1), extra])
    return reference.csr(int(e.max()) + 1, e)


def test_split_imbalance_where_the_tree_branches_inside_the_separator():
    """Separator 41 joins the two parts; separator vertex 40 hangs off
    separator vertex 42 alone, so the elimination tree branches at its
    root into {40} and the rest, and the tree reads nearly all vertices
    as one side.  The order reads the parts."""
    xadj, adjncy = _two_paths(20, [[19, 41], [20, 41], [41, 42], [40, 42]])
    perm = np.arange(43)
    assert reference.top_imbalance(xadj, adjncy, perm) == \
        pytest.approx(40 / 43)
    assert reference.split_imbalance(xadj, adjncy, perm) == \
        pytest.approx(1 / 43)


def test_split_imbalance_leaves_out_a_piece_the_separator_cuts_off():
    """Vertex 302 hangs off separator vertex 301 alone and is ordered
    first: the last separator vertex frees it before the separator frees
    the parts.  One vertex of 303 is under ``n / PIECE``, no side."""
    xadj, adjncy = _two_paths(150, [[149, 300], [150, 300], [0, 301],
                                    [299, 301], [301, 302]])
    perm = np.concatenate([[302], np.arange(302)])
    assert reference.split_imbalance(xadj, adjncy, perm) == \
        pytest.approx(1 / 303)
    assert reference.top_imbalance(xadj, adjncy, perm) > 0.95


def test_split_imbalance_reads_1_with_no_separator_tail():
    # a path in its own order: every prefix up to half of it is connected
    xadj, adjncy = reference.csr(7, np.stack([np.arange(6),
                                              np.arange(1, 7)], 1))
    assert reference.split_imbalance(xadj, adjncy, np.arange(7)) == 1.0
    # the tree reads the whole path as one chain of separators
    assert reference.top_imbalance(xadj, adjncy, np.arange(7)) == 0.0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seed", [5, 6])
@pytest.mark.parametrize("size", [9, 10])
def test_split_imbalance_equals_top_imbalance_on_grids(size, seed, dtype):
    """On the 27-point grids of ``fe3d27``'s family the separator is one
    chain in the tree, and both readings agree to the last digit, in the
    reference's float32 and in the bfloat16 control."""
    import ml_dtypes
    req = pool.build_set(dict(MESH, nx=size, ny=size, nz=size), seed, 1)[0]
    xadj, adjncy = reference.csr(req.n, req.edges)
    perm = reference.nested_dissection(
        xadj, adjncy, dtype=np.float32 if dtype == "float32"
        else ml_dtypes.bfloat16)
    assert reference.split_imbalance(xadj, adjncy, perm) == \
        reference.top_imbalance(xadj, adjncy, perm)


def test_is_permutation_rejects_repeats_and_range():
    assert reference.is_permutation(np.array([2, 0, 1]), 3)
    assert not reference.is_permutation(np.array([0, 0, 1]), 3)
    assert not reference.is_permutation(np.array([0, 1, 3]), 3)
    assert not reference.is_permutation(np.array([0, 1]), 3)
    assert not reference.is_permutation(np.array([0.0, 1.0, 2.0]), 3)


def _result(records, t_open=0.0, t_close=10.0):
    return loop.LoopResult(records, t_open, t_close, t_close + 1.0,
                           len(records))


def test_rate_over_the_whole_window_and_tail_over_all_requests():
    """The rate counts every answer inside the window over all of its
    time; a window request answered after the close and the tail's
    requests do not count."""
    run = _run_module()
    R = loop.Record
    ok = types.SimpleNamespace(status="ok", perm=None)
    recs = [R(i, 0, 100, t_submit=float(i), counted=True,
              t_resolve=float(i) + 0.5, result=ok) for i in range(3)]
    recs.append(R(3, 0, 100, 9.0, True, t_resolve=12.0, result=ok))
    recs.append(R(4, 0, 100, 9.5, False, t_resolve=13.0, result=ok))
    view = run.RunView(_result(recs), [], None, None, [], {})
    e2e = run.end_to_end(view, 5.0)
    # three answered inside the 10 s window; the idle time counts too
    assert e2e == pytest.approx({"setup_s": 5.0,
                                 "vertices_per_s": 300 / 10.0})
    assert [r.index for r in view.requests] == [0, 1, 2, 3]
    recs[1].result = types.SimpleNamespace(status="failed", perm=None)
    view = run.RunView(_result(recs), [], None, None, [], {})
    assert run.end_to_end(view, 5.0)["vertices_per_s"] == \
        pytest.approx(200 / 10.0)


class _Service:
    """A stand-in service: each request resolves ``delay`` pumps after
    it was submitted."""

    def __init__(self, delay=2):
        self.delay, self.queue, self.pumps, self.rids = delay, {}, 0, 0

    def submit(self, graph, seed, nproc):
        rid, self.rids = self.rids, self.rids + 1
        self.queue[rid] = self.pumps + self.delay
        return rid

    def poll(self, rid):
        return None

    def pump(self):
        self.pumps += 1
        due = [r for r, p in self.queue.items() if p <= self.pumps]
        for r in due:
            del self.queue[r]
        return {r: types.SimpleNamespace(status="ok", perm=None)
                for r in due}

    def queue_depth(self):
        return len(self.queue)


def test_tail_starts_at_a_request_boundary_and_runs_whole_orderings():
    reqs = [types.SimpleNamespace(index=i, n=10, seed=i) for i in range(20)]
    seen = []
    res = loop.closed_loop(
        _Service(), reqs, [None] * 20, 1, 1,
        close=lambda t, done: done >= 3, tail=2,
        on_tail=lambda what, t: seen.append((what, t)))
    assert [w for w, _ in seen] == ["start", "end"]
    assert res.tail == (seen[0][1], seen[1][1])
    assert res.t_end == res.tail[0]
    counted = [r for r in res.records if r.counted]
    assert len(counted) == 3 and all(r.t_resolve <= res.t_close
                                     for r in counted)
    # the tail orders whole requests: submitted at or after its start
    # (the one the last window answer set off) and answered inside it
    tail = [r for r in res.records if r.t_resolve is not None
            and res.tail[0] < r.t_resolve <= res.tail[1]]
    assert len(tail) == 2 and all(not r.counted for r in tail)
    assert all(r.t_submit >= res.t_close for r in tail)
    # without a tail the driver stops at the window's end
    res = loop.closed_loop(_Service(), reqs, [None] * 20, 1, 1,
                           close=lambda t, done: done >= 3)
    assert res.tail is None and res.t_end == res.t_close


def test_warm_up_stops_after_a_steady_round_covering_the_window():
    run = _run_module()

    class Ev:
        events = []
    w = run.WarmUp(Ev, clients=1, seconds=10.0, extra=1)
    Ev.events.append((0.0, "stage", {"compile": True}))
    assert not w(1.0, 1)                # built an executable: no rate yet
    assert w.need is None
    Ev.events.append((0.0, "stage", {"compile": True}))
    assert not w(2.0, 2)
    assert w.need is None
    assert w.longest is None            # no steady round timed yet
    assert not w(3.0, 3)                # steady: 1 per s -> 15 + 1
    assert w.need == 16 and w.longest == 1.0
    assert not w(15.0, 15)
    Ev.events.append((0.0, "stage", {"compile": True}))
    assert w(16.0, 16)                  # the window's graphs are warmed


def test_setup_budget_leaves_room_for_window_late_wait_tail_and_check():
    run = _run_module()
    late = loop.LATE_S
    assert run.setup_budget(360.0, 51.0, 3.0) == 360 - 51 - 6 - 45
    assert run.setup_budget(360.0, 51.0, None) == 360 - 51 - 2 * late - 45
    assert run.setup_budget(1200.0, 51.0, 2 * late) == \
        1200 - 51 - 2 * late - 45


def test_lattice_counts_executables_and_lane_pads():
    run = _run_module()

    def launch(kind, bucket, rounds, lanes_pad):
        return (0.0, "launch", {"kind": kind, "bucket": bucket,
                                "rounds": rounds, "lanes_pad": lanes_pad})
    events = [launch("fm", (2048, 256, 3, False), 3, 8),
              launch("fm", (2048, 256, 3, False), 3, 8),     # the same
              launch("fm", (2048, 256, 3, True), 3, 8),      # pos_only
              launch("fm", (2048, 256, 3, False), 3, 16),
              launch("bfs", (1024, 32), 3, 5),
              launch("bfs", (1024, 32), 4, 5),               # width
              launch("dbfs", (1024, 32), 3, 5),              # not listed
              (0.0, "stage", {"compile": True})]
    lat = run.lattice(events)
    assert set(lat) == {"fm", "bfs", "match"} and lat["match"] == {}
    assert {lp: len(k) for lp, k in lat["fm"][(2048, 256)].items()} == \
        {8: 2, 16: 1}
    assert {lp: len(k) for lp, k in lat["bfs"][(1024, 32)].items()} == {5: 2}


SYNTH = {
    "window": [0.0, 1000.0], "devices": ["/device:TPU:0"],
    "ops": [["fusion.1", -50.0, 150.0, 0], ["fusion.2", 300.0, 100.0, 0],
            ["while.3", 350.0, 200.0, 0], ["fusion.1", 900.0, 200.0, 0]],
    "modules": [["jit_fm_refine_multi(7)", 300.0, 250.0, 0],
                ["jit_bfs(2)", -50.0, 150.0, 0]],
    "host": [["sched:pump", 0.0, 1000.0], ["router:wave", 100.0, 500.0],
             ["dispatch:fm", 290.0, 270.0]],
}


def test_trace_reduction_on_a_made_up_trace():
    red = devtrace.reduce_trace(SYNTH)
    # busy: [0,100] + [300,550] + [900,1000] = 450 ns of 1000
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(450e-9)
    gaps = dict(red["breakdown"]["idle_gaps"])
    # a gap is named by the innermost span open at its middle:
    # [100,300] by router:wave, [550,900] by sched:pump
    assert gaps == pytest.approx({"router:wave": 200e-9,
                                  "sched:pump": 350e-9})
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(200e-9)
    assert devtrace.module_seconds(red, "fm_refine_multi") == \
        pytest.approx(250e-9)
    assert devtrace.reduce_trace(dict(SYNTH, window=None)) is None


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.trace.json")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_trace_reduction_on_a_recorded_trace(path):
    with open(path) as f:
        rec = json.load(f)
    red = devtrace.reduce_trace(rec["compact"])
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = sum(s for _, s in red["breakdown"]["idle_gaps"])
    assert len(red["breakdown"]["idle_gaps"]) <= 10
    if len(red["breakdown"]["idle_gaps"]) < 10:
        assert idle + red["busy_s"] == pytest.approx(red["window_s"])
    for key in ("busy_s", "window_s"):
        assert red[key] == pytest.approx(rec["reduced"][key])
    assert devtrace.module_seconds(red, "fm_refine_multi") == \
        pytest.approx(rec["reduced"]["fm_s"])


def test_peaks_known_device_and_unknown_is_an_error():
    assert kernel_bytes.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        kernel_bytes.peaks("TPU v99")


def test_fm_least_bytes_reads_the_table_once():
    one = kernel_bytes.fm_least_bytes(1, 4096, 32)
    assert one == 4096 * 32 * 4 + 4096 * 6 + 20 + 4096 + 8
    assert kernel_bytes.fm_least_bytes(8, 4096, 32) == 8 * one


def _view(**kw):
    base = dict(seconds=10.0, t_open=0.0, t_close=10.0, requests=[],
                completed=[], events=[], spans=None, trace=None,
                trace_events=[], peaks={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _reader(name):
    return spec.load_reader(CHIP, name)


def test_readers_on_a_made_up_window():
    launch = lambda lanes, pad: (1.0, "launch", {
        "kind": "fm", "lanes": lanes, "lanes_pad": pad,
        "bucket": (4096, 32, 3, False)})
    stage = lambda name, s, c=False: (1.0, "stage", {
        "name": name, "seconds": s, "compile": c})
    done = [types.SimpleNamespace(n=10)] * 2
    ev = [launch(6, 8), launch(8, 8), stage("fm", 2.0), stage("bfs", 0.5),
          stage("match", 0.25), stage("fm", 3.0, True)]
    v = _view(events=ev, completed=done)
    assert _reader("router.lane_fill")(v) == pytest.approx(87.5)
    assert _reader("router.launches_per_ordering")(v) == 1.0
    assert _reader("fm.dispatch_share")(v) == pytest.approx(20.0)
    assert _reader("bfs_match.dispatch_share")(v) == pytest.approx(7.5)
    assert _reader("compile.first_uses_in_window")(v) == 1.0
    sp = lambda name, t0, t1: types.SimpleNamespace(name=name, t0=t0, t1=t1)
    v.spans = [sp("dispatch:fm", -1.0, 2.0), sp("dispatch:bfs", 1.0, 3.0),
               sp("sched:pump", 0.0, 10.0), sp("dispatch:fm", 8.0, 12.0)]
    assert _reader("host.outside_dispatch_share")(v) == pytest.approx(50.0)
    assert _reader("device.idle_share")(v) is None
    assert _reader("fm_roofline")(v) is None
    v.trace = devtrace.reduce_trace(SYNTH)
    assert _reader("device.idle_share")(v) == pytest.approx(55.0)
    v.trace_events = [launch(8, 8)]
    least = kernel_bytes.fm_least_bytes(8, 4096, 32) / 819e9
    assert _reader("fm_roofline")(v) == pytest.approx(
        100 * least / 250e-9)


def test_readers_find_nothing_in_an_empty_window():
    v = _view()
    for name in ("router.lane_fill", "router.launches_per_ordering",
                 "fm.dispatch_share", "bfs_match.dispatch_share",
                 "host.outside_dispatch_share",
                 "device.idle_share", "fm_roofline"):
        assert _reader(name)(v) is None, name
