"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script itself refuses to run without a TPU; these tests call its
phases directly on small graphs (the default phase in-process, the
four-chip phase on 4 virtual host devices in a subprocess) and check
that the script fails, printing no result line, off the chip and
outside a checkout.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

from procutil import run_json_script

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_default_phase_tiny_on_cpu():
    from repro.graphs import generators as G
    cs = _load_smoke()
    lines = []
    out = cs.run_default(
        ("grid3d-8", G.grid3d(8, 8, 8)),
        {"grid2d-20": G.grid2d(20, 20), "circuit-500": G.circuit(500, seed=1),
         "rgg2d-400": G.rgg2d(400, seed=2)},
        log_fn=lines.append)
    assert out["opc_ratio"] <= cs.OPC_RATIO_MAX
    st = out["stats"]
    assert st["failed"] == st["shed"] == st["degraded"] == 0
    assert st["cache_hits"] == 4 and st["computed"] == 5
    assert any(s.startswith("collectives P=1") for s in lines)
    assert any(s.startswith("executables:") for s in lines)


def test_four_chip_phase_tiny_on_virtual_devices():
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import importlib.util, json
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {SMOKE!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.graphs import generators as G
        g = G.grid3d(10, 10, 10)
        out = cs.run_four_chips("grid3d-10", g, log_fn=lambda s: None)
        cs.run_collectives(G.grid2d(16, 16), 4, log_fn=lambda s: None)
        print(json.dumps({{"ratio": out["opc_ratio"]}}))
    """)
    out = run_json_script(script, timeout=400)
    assert out["ratio"] <= 1.05


def test_smoke_fails_without_tpu(capsys):
    cs = _load_smoke()
    assert cs.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_smoke_fails_outside_checkout(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
