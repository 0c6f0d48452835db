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
import shutil
import subprocess
import sys

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
