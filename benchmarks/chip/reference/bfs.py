"""Plain breadth-first search hop counts over a CSR built from the edge list.

Independent of the program under test. ``hops[v]`` is the number of edges
on a shortest path from the source to ``v``, and -1 where ``v`` cannot be
reached.
"""
from __future__ import annotations

import numpy as np


class CSR:
    """Out-neighbours of every vertex, from an edge list."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        order = np.argsort(src, kind="stable")
        self.n = n
        self.nbrs = np.asarray(dst)[order]
        counts = np.bincount(np.asarray(src), minlength=n)
        self.indptr = np.zeros(n + 1, np.int64)
        np.cumsum(counts, out=self.indptr[1:])

    def expand(self, frontier: np.ndarray) -> np.ndarray:
        """All out-neighbours of the frontier, with repeats."""
        starts = self.indptr[frontier]
        counts = self.indptr[frontier + 1] - starts
        total = int(counts.sum())
        offsets = np.repeat(starts - np.cumsum(counts) + counts, counts)
        return self.nbrs[offsets + np.arange(total)]


def levels(csr: CSR, source: int):
    """Yield ``(level, vertices first reached at that level)``."""
    seen = np.zeros(csr.n, bool)
    seen[source] = True
    frontier = np.array([source], np.int64)
    level = 0
    yield level, frontier
    while len(frontier):
        level += 1
        nb = csr.expand(frontier)
        nb = np.unique(nb[~seen[nb]])
        seen[nb] = True
        frontier = nb
        if len(frontier):
            yield level, frontier


def hops(csr: CSR, source: int) -> np.ndarray:
    """(n,) hop count from ``source``; -1 where unreachable."""
    out = np.full(csr.n, -1, np.int64)
    for level, verts in levels(csr, source):
        out[verts] = level
    return out


def hops_one_level_short(csr: CSR, source: int) -> np.ndarray:
    """The control: the same search with its farthest level left out, as a
    search that stops one superstep early would leave it."""
    found = list(levels(csr, source))
    out = np.full(csr.n, -1, np.int64)
    for level, verts in found[:-1] if len(found) > 1 else found:
        out[verts] = level
    return out
