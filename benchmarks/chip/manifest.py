"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration names
its graph's generator, the mix its kind and its check; a metric names
itself. Each lives in a file of its own under the benchmark's directory
(the first of ``paths``), so a new cell, configuration, generator, traffic
mix or kind, check or metric is a new file plus an entry in
``BENCHMARK.json``, and never an edit of an existing file:

- ``configs/<config>.json`` (the path is the configuration's ``file``),
- ``generators/<graph.generator>.py``, with ``make(spec) -> Graph``
  (``graphs.py``),
- ``traffic/<traffic>.json``, a mix of parameters,
- ``traffic/<kind>.py``, the generator of a kind of mix (``drive.py``),
- ``checks/<check>.py``, with ``PROGRAMS`` and ``judge`` (``check.py``),
- ``metrics/<metric>.py``, with ``read(run) -> float | None``.

An unknown name, and a mix whose program its check does not list, are
refused before anything runs.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re
import types
from typing import Any, Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class UnknownName(KeyError):
    """A cell, configuration, traffic mix or metric that is not defined."""


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise UnknownName(f"no {what} named {name!r} in BENCHMARK.json")


class Manifest:
    def __init__(self, data: dict, root: pathlib.Path = ROOT,
                 here: pathlib.Path = HERE):
        self.data = data
        self.root = root
        self.here = here

    @classmethod
    def load(cls, path: Optional[pathlib.Path] = None) -> "Manifest":
        path = pathlib.Path(path or ROOT / "BENCHMARK.json")
        data = json.loads(path.read_text())
        return cls(data, root=path.parent,
                   here=path.parent / data["paths"][0])

    def module(self, kind: str, name: str) -> types.ModuleType:
        """``<kind>/<name>.py`` under the benchmark's directory."""
        path = self.here / kind / f"{name}.py"
        if not path.is_file():
            raise UnknownName(f"no {kind} file {kind}/{path.name}")
        tag = re.sub(r"\W", "_", f"chip_{kind}_{name}")
        spec = importlib.util.spec_from_file_location(tag, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def cell(self, name: str) -> dict:
        return _by_name(self.data["workloads"], name, "workload")

    def config(self, name: str) -> dict:
        entry = _by_name(self.data["configs"], name, "configuration")
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        path = self.here / "traffic" / f"{name}.json"
        if not path.is_file():
            raise UnknownName(f"no traffic mix file {path.name}")
        return json.loads(path.read_text())

    def generator(self, config: dict) -> types.ModuleType:
        """The module that makes the configuration's graph."""
        return self.module("generators", config["graph"]["generator"])

    def kind(self, traffic: dict) -> types.ModuleType:
        """The generator of the mix's kind of traffic."""
        return self.module("traffic", traffic["kind"])

    def check(self, traffic: dict) -> types.ModuleType:
        """The mix's check, which must list the mix's program."""
        module = self.module("checks", traffic["check"])
        if traffic["program"] not in module.PROGRAMS:
            raise UnknownName(f"check {traffic['check']!r} has no reference "
                              f"for program {traffic['program']!r}")
        return module

    def end_to_end(self, cell: str) -> List[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.data["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[dict]:
        """The per-layer metrics this cell reports: those that list it,
        and those without a list whose end-to-end metric it reports."""
        reported = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]

    def reader(self, metric: str) -> Callable[[Any], Optional[float]]:
        return self.module("metrics", metric).read

    def names(self) -> Dict[str, List[str]]:
        return {key: [e["name"] for e in self.data[key]]
                for key in ("configs", "workloads", "end_to_end",
                            "per_layer")}
