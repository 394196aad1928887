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


SCOPED = """{pad}
import jax, jax.numpy as jnp
from repro import compile_cache
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
def f(x):
    with jax.named_scope("{name}"):
        return x * 2 + 1
text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
print("{name}" in text)
"""


def test_cached_executables_keep_their_own_scope_names(tmp_path):
    """Two programs that differ only in their scope names share no cache
    entry: the second compiles, and reads back its own names. The same
    program with its lines moved finds the first one's entry."""
    cache = tmp_path / "jax_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=str(ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    entries = []
    for name, pad in (("first_scope", 0), ("second_scope", 0),
                      ("second_scope", 7)):
        proc = subprocess.run(
            [sys.executable, "-c", SCOPED.format(name=name, pad="\n" * pad)],
            env=env, cwd=str(tmp_path), capture_output=True, text=True,
            timeout=300)
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.split()[-1] == "True", (name, proc.stdout)
        entries.append({p.name for p in cache.iterdir()
                        if p.name.startswith("jit_f-")})
    assert len(entries[1]) == len(entries[0]) + 1
    assert entries[2] == entries[1]
