"""End-to-end runs of the chip benchmark on the CPU at a tiny size.

``run.main(require_chip=False)`` skips the look for a TPU and drives the
rest of a run: set-up, warm-up, window, check and result line.  The
cells here live in a copy of the benchmark made in a temporary
directory, so adding a configuration, a traffic mix and a metric is done
with files alone, as a later change would.
"""
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{name}", os.path.join(CHIP, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.path.insert(0, CHIP)
    spec.loader.exec_module(mod)
    return mod


run = _load("run")
import check  # noqa: E402  (importable once run.py put CHIP on sys.path)
import loop  # noqa: E402

TINY_MESH = {"name": "tinymesh", "generator": "grid3d", "nx": 10,
             "ny": 10, "nz": 10, "stencil": 27, "nproc": 4, "set_seed": 3,
             "set_size": 40, "set_block": 3,
             "check": {"sample": 2, "opc_ratio": 2.0, "top_imbalance": 0.35}}
SOLO = {"loop": "closed", "clients": 1}


def make_root(tmp_path, cells, configs, traffic, metrics=None):
    """A checkout holding the benchmark, the program and ``cells``."""
    root = tmp_path / "checkout"
    shutil.copytree(CHIP, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), root / "src")
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for name, cfg in configs.items():
        path = f"benchmarks/chip/configs/{name}.json"
        (root / path).write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": path, "reduced": [], "why": "t"})
    for name, mix in traffic.items():
        (root / "benchmarks/chip/traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    for name, body in (metrics or {}).items():
        (root / "benchmarks/chip/metrics" / f"{name}.py").write_text(body)
        bench["per_layer"].append(
            {"name": name, "unit": "vertices", "better": "higher",
             "source": "program_counter", "layer": "test",
             "moves": "vertices_per_s", "workloads": list(cells)})
    for name, (config, mix) in cells.items():
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1, "why": "t"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def run_cell(root, cell, capsys, seed=11, seconds=1.0, trace=0):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  require_chip=False)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]) if rc == 0 else None


@pytest.fixture(autouse=True)
def run_age(monkeypatch):
    """A run driven in this process counts its set-up from its test's
    start, not from the test process's start."""
    t0 = time.perf_counter()
    monkeypatch.setattr(run, "process_age", lambda: time.perf_counter() - t0)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"),
                     {"tinymesh.solo": ("tinymesh", "tsolo")},
                     {"tinymesh": TINY_MESH}, {"tsolo": SOLO})


def test_added_files_found_by_name(tmp_path, capsys):
    """A configuration, a traffic mix and a metric added as files, and a
    cell naming them, run with no change to the harness."""
    circ = {"name": "tinycirc", "generator": "circuit", "n_min": 300,
            "n_max": 500, "fanout": 2.4, "nproc": 2, "set_seed": 4,
            "set_size": 200, "set_block": 8,
            "check": {"sample": 2, "opc_ratio": 2.0}}
    pair = {"loop": "closed", "clients": 2}
    reader = ("def read(run):\n"
              "    return float(sum(r.n for r in run.completed))\n")
    root = make_root(tmp_path, {"tinycirc.pair": ("tinycirc", "pair")},
                     {"tinycirc": circ}, {"pair": pair},
                     {"test.vertices_done": reader})
    rc, res = run_cell(root, "tinycirc.pair", capsys, trace=1)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["test.vertices_done"]["value"] > 0
    assert list(res)[-1] == "check"
    rc, res = run_cell(root, "tinycirc.pair", capsys, trace=0)
    assert rc == 0 and res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "vertices_per_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


def _reverse_answers(monkeypatch):
    from repro.service import api
    orig = api.OrderingService._resolve

    def altered(self, rid, perm, *a, **k):
        return orig(self, rid, None if perm is None else perm[::-1].copy(),
                    *a, **k)
    monkeypatch.setattr(api.OrderingService, "_resolve", altered)


def _drop_half(monkeypatch):
    """Every other answer of a service, from its first, never comes."""
    from repro.service import api
    orig = api.OrderingService.pump
    seen = {}

    def pump(self, *a, **k):
        out = {}
        for rid, r in orig(self, *a, **k).items():
            n = seen[id(self)] = seen.get(id(self), 0) + 1
            if n % 2 == 0:
                out[rid] = r
        return out
    monkeypatch.setattr(api.OrderingService, "pump", pump)
    monkeypatch.setattr(loop, "LATE_S", 1.0)


def _fm_unchanged(monkeypatch):
    from repro.service import router

    def unchanged(works, gain_mode=None, mode=None):
        out = []
        for w in works:
            part = np.asarray(w.part if w.parts_init is None
                              else np.asarray(w.parts_init)[0], np.int8)
            vw = np.asarray(w.vwgt, float)
            out.append((part, float(vw[part == 2].sum()),
                        float(abs(vw[part == 0].sum()
                                  - vw[part == 1].sum()))))
        return out
    monkeypatch.setattr(router, "execute_fm_works", unchanged)


def _sound(monkeypatch):
    pass


@pytest.mark.parametrize("fault,correct", [
    (_sound, True),
    (_reverse_answers, False),      # an answer altered where it is made
    (_drop_half, False),            # half of the answers never come
    (_fm_unchanged, False),         # FM returns its state unchanged
])
def test_broken_timed_path_is_not_correct(tiny_root, capsys, monkeypatch,
                                          fault, correct):
    fault(monkeypatch)
    rc, res = run_cell(tiny_root, "tinymesh.solo", capsys)
    assert rc == 0
    assert res["correct"] is correct, res["check"]


def test_control_fails_the_limit():
    """Each control, in the program's place, reads over one of the
    configuration's limits: the reference in bfloat16 over
    ``top_imbalance``, the reference with its separators first over
    ``opc_ratio``."""
    import pool
    cfg = json.load(open(os.path.join(CHIP, "configs", "fe3d27.json")))
    limits = cfg["check"]
    for req in pool.build_set(dict(cfg, nx=10, ny=10, nz=10), 5, 2):
        low = check.readings(req.n, req.edges, None, "bfloat16")
        assert low["top_imbalance"] > limits["top_imbalance"]
        first = check.readings(req.n, req.edges, None, "separators_first")
        assert first["opc_ratio"] > limits["opc_ratio"]


#: circuits as a circuit configuration would send them (degree at most
#: 14-16, mean 6.6), with the limits proposed for its check; below about
#: 2,000 vertices the bfloat16 control keeps its balance, since its sums
#: of unit weights are exact to 256
CIRCUIT = {"name": "circuit", "generator": "circuit", "n_min": 2048,
           "n_max": 2400, "fanout": 2.4, "nproc": 4, "set_seed": 7,
           "set_size": 8, "set_block": 4,
           "check": {"sample": 1, "opc_ratio": 2.0, "split_imbalance": 0.35}}


def test_control_fails_the_limit_on_circuits():
    """On circuits the reference reads its top split from the order
    within FM's tolerance, the reference in bfloat16 reads over the
    ``split_imbalance`` limit, and separators first over ``opc_ratio``."""
    import pool
    import reference
    limits = CIRCUIT["check"]
    for req in pool.build_set(CIRCUIT, 5, 2):
        xadj, adjncy = reference.csr(req.n, req.edges)
        assert reference.split_imbalance(
            xadj, adjncy, reference.nested_dissection(xadj, adjncy)) <= 0.12
        low = check.readings(req.n, req.edges, None, "bfloat16")
        assert low["split_imbalance"] > limits["split_imbalance"]
        first = check.readings(req.n, req.edges, None, "separators_first")
        assert first["opc_ratio"] > limits["opc_ratio"]


def test_control_reads_a_configuration_file(tmp_path, capsys):
    """``control.py --config`` reads a configuration that no cell names:
    each control's worst readings beside the limits its ``check`` gives,
    the other readings as not compared."""
    control = _load("control")
    path = tmp_path / "circuit.json"
    path.write_text(json.dumps(CIRCUIT))
    assert control.main(["--config", str(path), "--seeds", "3"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["control"] for ln in lines] == list(control.CONTROLS)
    for ln in lines:
        assert ln["config"] == str(path) and ln["seed"] == 3
        assert set(ln["numbers"]) == {"opc_ratio", "split_imbalance"}
        assert set(ln["not_compared"]) == {"top_imbalance"}
        assert ln["fails"] is True
    with pytest.raises(SystemExit):
        control.main(["--workload", "fe3d27.solo", "--config", str(path),
                      "--seeds", "3"])


def test_check_logs_the_readings_it_does_not_compare(tiny_root, capsys):
    """A configuration whose ``check`` gives ``split_imbalance`` no limit
    compares what it did before; the log gives every reading of each
    sampled graph, and the worst ``split_imbalance`` as not compared,
    before the compared numbers that end it."""
    rc = run.main(["--workload", "tinymesh.solo", "--seed", "13",
                   "--seconds", "1", "--trace", "0"], root=tiny_root,
                  require_chip=False)
    out, err = capsys.readouterr()
    res = json.loads(out.splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    assert list(res["check"]) == ["unresolved", "failed", "invalid_perm",
                                  "opc_ratio", "top_imbalance"]
    lines = err.strip().splitlines()
    sampled = [ln for ln in lines if ln.startswith("check sample ")]
    assert len(sampled) == TINY_MESH["check"]["sample"]
    for ln in sampled:
        assert all(f"{name} " in ln for name in check.READINGS)
    apart = [i for i, ln in enumerate(lines) if re.fullmatch(
        r"check split_imbalance: [0-9.e-]+ \(not compared\)", ln)]
    assert len(apart) == 1 and apart[0] < len(lines) - 5
    assert [ln.split(":")[0] for ln in lines[-5:]] == [
        f"check {name}" for name in res["check"]]


def _result_lines(proc):
    return [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         "fe3d27.solo", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and not _result_lines(proc)
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "fe3d27.solo", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and not _result_lines(proc)


class _NeverSteady:
    """A service whose every answer pays a first use of a new FM
    executable (another ``lanes_pad`` each time): no round is steady."""

    PUMP_S = 0.1

    def __init__(self):
        self.queue, self.next, self.pumps = {}, 0, []

    def submit(self, graph, seed, nproc):
        self.next += 1
        self.queue[self.next] = graph
        return self.next

    def poll(self, rid):
        return None

    def queue_depth(self):
        return len(self.queue)

    def pump(self):
        from repro import obs
        self.pumps.append(time.perf_counter())
        time.sleep(self.PUMP_S)
        rid = min(self.queue)
        g = self.queue.pop(rid)
        obs.emit("stage", {"name": "fm", "seconds": self.PUMP_S,
                           "compile": True})
        obs.emit("launch", {"kind": "fm", "lanes": 1, "lanes_pad": 8 * rid,
                            "bucket": (128, 16, 3, False), "rounds": 3})
        return {rid: types.SimpleNamespace(status="ok", perm=np.arange(g.n))}


def test_setup_over_budget_exits_5_with_the_reason(tiny_root, capsys,
                                                   monkeypatch):
    """A warm-up that never has a steady round stops within one pump of
    its budget, with code 5, no result and the reason as its last
    lines; the budget keeps ``LATE_S`` twice while no round was
    steady."""
    import repro.service
    made = []

    def service():
        made.append(_NeverSteady())
        return made[-1]
    monkeypatch.setattr(repro.service, "OrderingService", service)
    monkeypatch.setattr(run, "RUN_LIMIT_S", 5.0)
    monkeypatch.setattr(run, "FIRST_RUN_LIMIT_S", 5.0)
    monkeypatch.setattr(run, "CHECK_RESERVE_S", 0.5)
    monkeypatch.setattr(loop, "LATE_S", 0.25)
    budget = 5.0 - 1.0 - 2 * 0.25 - 0.5
    rc = run.main(["--workload", "tinymesh.solo", "--seed", "5",
                   "--seconds", "1", "--trace", "0"], root=tiny_root,
                  require_chip=False)
    out, err = capsys.readouterr()
    assert rc == run.OVER_BUDGET == 5
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]
    last = err.strip().splitlines()[-5:]
    assert last[0].startswith("error: set-up ")
    assert float(re.search(r"budget of ([0-9.]+) s", last[0]).group(1)) \
        == pytest.approx(budget, abs=1e-3)
    age = float(last[1].split(": ")[1])
    pumps = made[0].pumps
    one_pump = max(b - a for a, b in zip(pumps, pumps[1:]))
    assert budget < age <= budget + one_pump + 0.1
    answers = int(last[3].split(": ")[1])
    assert answers == len(pumps) > 0
    assert last[2] == "first uses so far: traced " \
        f"{answers} ({answers * _NeverSteady.PUMP_S:.3f} s, " \
        f"{_NeverSteady.PUMP_S:.4f} s each)"
    assert last[4] == "steady round: none"
    assert f"lattice fm: {answers} executables" in err


def test_budget_leaves_a_small_cell_alone(tiny_root, capsys):
    """A cell whose set-up fits runs to its result, and its log lists the
    lattice of each launch kind and the budget it stayed under."""
    rc = run.main(["--workload", "tinymesh.solo", "--seed", "12",
                   "--seconds", "1", "--trace", "0"], root=tiny_root,
                  require_chip=False)
    out, err = capsys.readouterr()
    assert rc == 0 and json.loads(out.splitlines()[-1])["correct"]
    for kind in run.LATTICE_KINDS:
        assert re.search(rf"^lattice {kind}: [1-9][0-9]* executables; "
                         r"lanes_pad by n_pad x d_pad: \d+x\d+: \d",
                         err, re.M), kind
    used, budget = map(float, re.search(
        r"set-up budget: ([0-9.]+) s of ([0-9.]+) s", err).groups())
    assert 0 < used < budget


def test_first_run_of_a_cell_gets_the_longer_limit(tmp_path, capsys,
                                                   monkeypatch):
    """With an empty cache directory the run is the cell's first there
    and is held to ``FIRST_RUN_LIMIT_S``; the next run of the cell is
    held to ``RUN_LIMIT_S``."""
    root = make_root(tmp_path, {"tinymesh.solo": ("tinymesh", "tsolo")},
                     {"tinymesh": TINY_MESH}, {"tsolo": SOLO})
    monkeypatch.setattr(run, "RUN_LIMIT_S", 1.0)
    argv = ["--workload", "tinymesh.solo", "--seed", "14", "--seconds",
            "1", "--trace", "0"]
    assert run.main(argv, root=root, require_chip=False) == 0
    err = capsys.readouterr().err
    assert "the first run of tinymesh.solo with it, so the run's limit " \
        "is 1200 s" in err
    assert run.main(argv, root=root, require_chip=False) == 5
    err = capsys.readouterr().err
    assert "a later run of tinymesh.solo with it, so the run's limit is " \
        "1 s" in err
