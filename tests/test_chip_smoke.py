"""``chip_smoke.py`` rehearsed on the CPU: its phases at tiny sizes with
the Pallas kernels interpreted, its four-chip phase on four virtual CPU
devices, and its refusal to run where JAX finds no TPU."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("phase", ["placement", "simulator", "ensemble",
                                   "digests"])
def test_phase_passes_at_tiny_size(smoke, phase, capsys):
    run = {
        "placement": lambda: smoke.phase_placement(4096, 16, 64, 8,
                                                   interpret=True),
        "simulator": lambda: smoke.phase_simulator(64, 24, 48,
                                                   interpret=True),
        "ensemble": lambda: smoke.phase_ensemble(64, 24, 3, interpret=True),
        "digests": smoke.phase_digests,
    }[phase]
    run()
    out = capsys.readouterr().out
    assert "FAILED" not in out
    assert ": ok" in out
    if phase == "digests":
        assert out.count("match=True") == 2


def test_sharded_phase_on_four_cpu_devices():
    code = textwrap.dedent(f"""
        import importlib.util, sys
        sys.path.insert(0, {os.path.join(ROOT, "src")!r})
        spec = importlib.util.spec_from_file_location("s", {SMOKE!r})
        s = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(s)
        s.phase_sharded(64, 256, 24, interpret=True)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("lanes == unsharded lanes: ok") == 2
    assert "mesh(e, n)=(2, 2)" in r.stdout


def _no_ok_line(stdout):
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok") is True:
                return False
        except (ValueError, AttributeError):
            continue
    return True


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SMOKE], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert _no_ok_line(r.stdout)


def test_refuses_outside_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, lone)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(lone)], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert _no_ok_line(r.stdout)
