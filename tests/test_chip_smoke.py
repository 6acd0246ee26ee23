"""chip_smoke.py refuses to pass off the chip, and the compile-cache
helper puts JAX's persistent cache where it says."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, *, cwd, env=None, timeout=120):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _ok_lines(stdout):
    return [l for l in stdout.splitlines() if '"ok"' in l]


def test_chip_smoke_refuses_a_cpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run([SMOKE], cwd=REPO, env=env)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert not _ok_lines(proc.stdout)


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run(["chip_smoke.py"], cwd=tmp_path, env=env)
    assert proc.returncode != 0
    assert "repro" in proc.stderr
    assert not _ok_lines(proc.stdout)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_lands_in_one_place(tmp_path, from_env):
    """With JAX_COMPILATION_CACHE_DIR set the cache is written there;
    without it, to <repo>/.jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    want = str(tmp_path / "cache") if from_env \
        else os.path.join(REPO, ".jax_cache")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.launch import train
        path = train.use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.jit(lambda x: jnp.sin(x) * 3 + 7)(jnp.ones(5)) \\
            .block_until_ready()
        print(json.dumps({"path": path,
                          "config": jax.config.jax_compilation_cache_dir}))
    """)
    repo_cache = os.path.join(REPO, ".jax_cache")
    listing = lambda d: set(os.listdir(d)) if os.path.isdir(d) else set()
    before = listing(repo_cache)
    proc = _run(["-c", code], cwd=str(tmp_path), env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["path"] == out["config"] == want
    assert listing(want)
    if from_env:
        assert listing(repo_cache) == before
    assert sorted(os.listdir(tmp_path)) == (["cache"] if from_env else [])
