"""Where the persistent compilation cache lands (lighthouse_tpu/ops):
JAX_COMPILATION_CACHE_DIR when set, and nothing else; otherwise one fixed
directory inside the checkout, whatever the working directory."""

import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _cache_dir_after_import(cwd, **env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax, lighthouse_tpu.ops; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_env_var_wins(tmp_path):
    got = _cache_dir_after_import(
        ROOT, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert got == str(tmp_path / "cache")


def test_default_is_one_fixed_path_in_the_checkout(tmp_path):
    assert _cache_dir_after_import(ROOT) == str(ROOT / ".jax_cache")
    assert _cache_dir_after_import(tmp_path) == str(ROOT / ".jax_cache")
