"""The harness finds everything by name, runs a cell defined by data alone,
and refuses to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import manifest
import run
from conftest import CHIP, ROOT, result_line, write_manifest


def test_every_name_resolves():
    m = manifest.Manifest.load()
    names = m.names()
    for cell in names["workloads"]:
        entry = m.cell(cell)
        config = m.config(entry["config"])
        assert config["name"] == entry["config"]
        assert callable(m.generator(config).make)
        traffic = m.traffic(entry["traffic"])
        assert callable(m.kind(traffic).Driver)
        assert callable(m.check(traffic).judge)
        reported = {x["name"] for x in m.end_to_end(cell)}
        assert "setup_s" in reported and len(reported) >= 2
        assert m.per_layer(cell)
    for metric in names["end_to_end"] + names["per_layer"]:
        assert callable(m.reader(metric))


def test_per_layer_metrics_move_a_reported_metric():
    m = manifest.Manifest.load()
    for cell in m.names()["workloads"]:
        reported = {x["name"] for x in m.end_to_end(cell)}
        for metric in m.per_layer(cell):
            assert metric["moves"] in reported, (cell, metric["name"])


def _config(m):
    return m.config("g500-s20-cc")


def _mix(m, **change):
    return dict(m.traffic("sv-jobs"), **change)


@pytest.mark.parametrize("what,call", [
    ("workload", lambda m: m.cell("no-such-cell")),
    ("configuration", lambda m: m.config("no-such-config")),
    ("traffic", lambda m: m.traffic("no-such-mix")),
    ("metric", lambda m: m.reader("no_such_metric")),
    ("generator", lambda m: m.generator(
        {"graph": dict(_config(m)["graph"], generator="no_such_graph")})),
    ("kind", lambda m: m.kind(_mix(m, kind="no_such_kind"))),
    ("check", lambda m: m.check(_mix(m, check="no_such_check"))),
    ("program without a reference",
     lambda m: m.check(_mix(m, program="pagerank:basic"))),
    ("bfs check of a components program",
     lambda m: m.check(_mix(m, check="bfs_hops"))),
])
def test_unknown_names_are_refused(what, call):
    with pytest.raises(manifest.UnknownName):
        call(manifest.Manifest.load())


def test_generator_refuses_keys_it_does_not_read():
    m = manifest.Manifest.load()
    spec = _config(m)["graph"]
    make = m.generator(_config(m)).make
    for bad in (dict(spec, noise=1), dict(spec, undirected=False)):
        with pytest.raises(ValueError):
            make(bad)


GRID = '''"""A rows x cols grid, each vertex joined to its four neighbours."""
import numpy as np

import graphs


def make(spec):
    rows, cols = spec["rows"], spec["cols"]
    ids = np.arange(rows * cols).reshape(rows, cols)
    pairs = np.concatenate([
        np.stack([ids[:, :-1].ravel(), ids[:, 1:].ravel()], 1),
        np.stack([ids[:-1].ravel(), ids[1:].ravel()], 1)])
    keys = np.unique(np.concatenate([pairs[:, 0] * rows * cols + pairs[:, 1],
                                     pairs[:, 1] * rows * cols + pairs[:, 0]]))
    n = rows * cols
    return graphs.Graph(n, (keys // n).astype(np.int32),
                        (keys % n).astype(np.int32))
'''


def test_a_cell_defined_by_data_alone(tmp_path, cpu_devices, capsys):
    """A new graph generator, configuration and traffic mix (with a program
    no cell ran before), each a new file, and new entries in
    BENCHMARK.json make a cell: no existing file changes."""
    path = write_manifest(tmp_path)
    chip = tmp_path / "benchmarks" / "chip"
    (chip / "generators" / "grid2d.py").write_text(GRID)
    config = {"name": "grid-cc", "graph": {"generator": "grid2d", "rows": 24,
                                           "cols": 40, "seed": 0},
              "workers": 4, "partitioner": "random", "partition_seed": 0,
              "engine": {"mode": "fused"}}
    (chip / "configs" / "grid-cc.json").write_text(json.dumps(config))
    (chip / "traffic" / "sv-basic-jobs.json").write_text(json.dumps(
        {"kind": "jobs", "program": "sv:basic", "check": "components"}))
    bench = json.loads(path.read_text())
    bench["configs"].append({"name": "grid-cc", "source": "test",
                             "file": "benchmarks/chip/configs/grid-cc.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "grid-sv-basic", "config": "grid-cc",
                               "traffic": "sv-basic-jobs", "chips": 1,
                               "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "cc-sv" in metric.get("workloads", []):
            metric["workloads"].append("grid-sv-basic")
    path.write_text(json.dumps(bench))
    for trace in (0, 1):
        assert run.main(["--workload", "grid-sv-basic", "--seed", "9",
                         "--seconds", "0.2", "--trace", str(trace)],
                        devices=cpu_devices, manifest_path=path) == 0
        line = result_line(capsys)
        assert line["correct"] is True and line["attempted"] >= 1
        assert list(line)[-1] == "checks"
        assert line["checks"]["label_mismatch"]["value"] == 0
        if trace:
            assert "supersteps.job" in line["metrics"]
            assert line["device"]["window_s"] > 0
        else:
            assert {"setup_s", "job_s", "peak_hbm_gb"} <= set(line["metrics"])


def _run_cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "cc-sv",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_no_tpu_no_result():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
