"""``chip_smoke.py`` refuses to report from anything but a TPU, and the
compile-cache helper picks its directory as documented."""
import os
import shutil
import subprocess
import sys

import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("alone", [False, True],
                         ids=["checkout", "script_alone"])
def test_chip_smoke_refuses_cpu(alone, tmp_path):
    """On the CPU — in the checkout, or copied where none of the repo is
    — the script exits non-zero and prints no ``ok`` line."""
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    if alone:
        cwd = str(tmp_path / "alone")
        os.makedirs(cwd)
        script = shutil.copy(script, cwd)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0, out.stdout[-2000:]
    assert '"ok": true' not in out.stdout


@pytest.mark.parametrize("env,expect", [
    ({"JAX_COMPILATION_CACHE_DIR": "/shared/jax-cache"},
     ("/shared/jax-cache", False)),
    ({}, (os.path.join(REPO, ".jax_cache"), True)),
    ({"JAX_COMPILATION_CACHE_DIR": ""}, (os.path.join(REPO, ".jax_cache"),
                                         True)),
], ids=["env_set", "env_unset", "env_empty"])
def test_compile_cache_dir_choice(env, expect):
    """Set, the environment's directory wins and code sets nothing;
    unset, the cache goes to the checkout's fixed, git-ignored
    ``.jax_cache``."""
    assert compile_cache.cache_dir(env) == expect
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
