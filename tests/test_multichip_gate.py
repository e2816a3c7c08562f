"""The driver's multichip configuration, exactly.

``dryrun_multichip`` runs in a fresh process configured for the CPU
(``JAX_PLATFORMS=cpu`` + 8 virtual devices), the way a builder rehearses
a multi-chip path without chips. With fewer devices than asked it must
raise — never swap in another backend's devices (ref test model: every
test under ``mpirun -np {1,2,4}``, ``cpp/test/CMakeLists.txt:44-50``).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dryrun(n: int):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [REPO, env.get("PYTHONPATH", "")] if p)
    code = (f"import __graft_entry__ as g; g.dryrun_multichip({n}); "
            "print('GATE-OK')")
    return subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("n,ok", [(8, True), (16, False)])
def test_dryrun_multichip_driver_config(n, ok):
    r = _dryrun(n)
    if ok:
        assert r.returncode == 0, f"stderr tail:\n{r.stderr[-4000:]}"
        assert "GATE-OK" in r.stdout
    else:
        assert r.returncode != 0
        assert "need 16 devices, have 8 (cpu)" in r.stderr
