"""The graphs the benchmark runs on: an undirected edge list, made by the
generator that a configuration names (``generators/<name>.py``), and kept
in a file after its first generation.

A generator module defines ``make(spec) -> Graph``, where ``spec`` is the
configuration's ``graph`` object; it refuses a key it does not read.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pathlib
from typing import Callable

import numpy as np


@dataclasses.dataclass
class Graph:
    """An undirected graph: both directions of every edge, sorted by
    (src, dst), no self-loops, no duplicates."""

    n: int
    src: np.ndarray  # (E,) int32
    dst: np.ndarray  # (E,) int32

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])

    def degrees(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one named use of a seed (search keys, traffic
    order), independent of a generator's bits."""
    return np.random.default_rng([stream, seed])


def cached(spec: dict, directory: pathlib.Path,
           make: Callable[[dict], Graph]) -> Graph:
    """``make(spec)``, kept in a file of ``directory`` named by the
    generator and a digest of the whole spec, and read from there after
    the first call."""
    digest = hashlib.sha256(
        json.dumps(spec, sort_keys=True).encode()).hexdigest()[:16]
    path = directory / f"{spec['generator']}-{digest}.npz"
    if path.is_file():
        with np.load(path) as f:
            return Graph(int(f["n"]), f["src"], f["dst"])
    graph = make(spec)
    directory.mkdir(parents=True, exist_ok=True)
    part = path.with_name(f"{path.name}.{os.getpid()}.part")
    with open(part, "wb") as f:
        np.savez(f, n=graph.n, src=graph.src, dst=graph.dst)
    os.replace(part, path)
    return graph


def search_keys(graph: Graph, seed: int, count: int) -> np.ndarray:
    """``count`` search keys drawn uniformly, with replacement, among the
    vertices of degree >= 1 (Graph500 kernel 2)."""
    candidates = np.flatnonzero(graph.degrees() > 0)
    rng = host_rng(seed, 1)
    return candidates[rng.integers(0, len(candidates), count)].astype(np.int64)
