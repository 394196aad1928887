"""Where the entry points put JAX's persistent compilation cache."""
import os
import pathlib
import subprocess
import sys

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_default_dir_is_fixed_in_the_checkout():
    assert compile_cache.default_dir({}) == str(ROOT / ".jax_cache")
    # a fixed path: the same on every call, in every process
    assert compile_cache.default_dir({}) == compile_cache.default_dir({})


def test_environment_dir_is_left_to_jax():
    env = {compile_cache.ENV: "/somewhere/else"}
    assert compile_cache.default_dir(env) is None


def test_cli_compiles_into_the_environment_dir(tmp_path):
    """``python -m repro run`` caches where JAX_COMPILATION_CACHE_DIR
    says (the 1 s minimum compile time is dropped so a small CPU compile
    is written at all)."""
    cache = tmp_path / "jax_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro", "run", "wcc:basic", "--scale", "6"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "oracle: ok" in proc.stdout
    assert any(cache.iterdir())
