"""``chip_smoke.py`` rehearsed on the CPU at ``scaled(64)``.

The script's phases are plain functions, so the same code that runs on
the chip at Frontier's 9,600 nodes runs here on a 64-node Frontier; the
four-chip phase runs in a child process on four virtual CPU devices. On
a CPU device ``main`` refuses to run at all.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_pass_at_scaled_64_and_main_refuses_cpu(capsys):
    smoke = _load_chip_smoke()
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""          # no result without a TPU

    smoke.phase_simulate(scale=64, hours=2)
    smoke.phase_sweep(scale=64, hours=2)
    smoke.phase_serve(scale=64, hours=2)
    out = capsys.readouterr().out
    assert "FAILED" not in out
    assert out.count("check sweep row") == 2 * len(smoke.SWEEP_PAIRS)
    assert "check simulate replay schedule: ok" in out
    assert "check serve neutral fork: ok" in out

    prog = ("import chip_smoke; "
            "chip_smoke.phase_four_chips(scale=64, hours=2)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    child = subprocess.run([sys.executable, "-c", prog], cwd=ROOT, env=env,
                           capture_output=True, text=True, timeout=600)
    assert child.returncode == 0, child.stderr[-2000:]
    assert "FAILED" not in child.stdout
    assert "check four-chip sweep sharded: ok" in child.stdout
    assert child.stdout.count("check four-chip row") == \
        2 * len(smoke.SWEEP_PAIRS)
