"""Where the persistent compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
when set (the package then sets no directory), else the fixed
``<checkout>/.jax_cache``. Checked in a fresh process, since the
package configures the cache as it is imported."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("outside", [True, False])
def test_compile_cache_dir(tmp_path, outside):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if outside:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import cylon_tpu, jax; "
            "print(jax.config.jax_compilation_cache_dir)")
    r = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    want = str(tmp_path) if outside else os.path.join(REPO, ".jax_cache")
    assert r.stdout.strip().splitlines()[-1] == want
