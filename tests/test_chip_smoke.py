"""``chip_smoke.py`` reports only from a TPU, and the persistent compile
cache lands in one fixed place.

Each case runs a fresh interpreter: the JAX platform and the cache
directory are process-wide settings."""
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _python(args, *, cwd, env):
    env = {**{k: v for k, v in os.environ.items()
              if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}, **env}
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300,
    )


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    """On the CPU, or copied away from the rest of the repo, the script
    exits non-zero and prints no result line."""
    cwd = ROOT
    if where == "alone":
        cwd = tmp_path / "alone"
        cwd.mkdir()
        shutil.copy(ROOT / "chip_smoke.py", cwd)
    r = _python(
        ["chip_smoke.py"], cwd=cwd,
        env={"JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
    )
    assert r.returncode != 0, r.stdout
    assert '"ok"' not in r.stdout


_CACHE_PROBE = textwrap.dedent(
    """
    import os, jax
    from repro.runtime.compile_cache import enable_compile_cache
    print(enable_compile_cache())
    print(jax.config.jax_compilation_cache_dir)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()
    """
)


@pytest.mark.parametrize("env_dir", [True, False], ids=["env_dir", "default"])
def test_compile_cache_directory(env_dir, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and compiled programs land there;
    without it the cache is ``<repo>/.jax_cache``, which git ignores."""
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src")}
    want = ROOT / ".jax_cache"
    if env_dir:
        want = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    r = _python(["-c", _CACHE_PROBE], cwd=tmp_path, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(want), str(want)]
    if env_dir:
        assert any(want.iterdir())  # the compiled program was written there
    else:
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
