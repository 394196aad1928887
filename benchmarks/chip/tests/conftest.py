"""Shared set-up of the harness's own tests (run by hand, on the CPU):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

They are outside the repository's ``tests`` path, so the tier-1 suite
does not collect them.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys
import tempfile

import pytest

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
for path in (CHIP, ROOT / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the harness turns JAX's persistent cache on; keep the tests' entries out
# of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    tempfile.gettempdir(), "chip-bench-tests-jax-cache"))


def write_manifest(tmp_path: pathlib.Path, scale: int = 9) -> pathlib.Path:
    """A checkout of the benchmark under ``tmp_path``: ``BENCHMARK.json``
    and the files under its ``paths``, with every configuration's graph cut
    to ``scale``."""
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip", ignore=
                    shutil.ignore_patterns("tests", ".graphs", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        path = tmp_path / entry["file"]
        config = json.loads(path.read_text())
        config["graph"]["scale"] = scale
        path.write_text(json.dumps(config))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


@pytest.fixture(autouse=True)
def graphs_in_tmp(tmp_path_factory, monkeypatch):
    """Keep the graphs that runs generate out of the checkout."""
    import run

    monkeypatch.setattr(run, "GRAPHS", tmp_path_factory.getbasetemp() / "graphs")


@pytest.fixture
def small_manifest(tmp_path):
    return write_manifest(tmp_path)


@pytest.fixture
def cpu_devices():
    import jax

    return jax.devices()


def result_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
