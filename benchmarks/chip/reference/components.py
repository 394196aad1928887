"""Plain connected components: min-label propagation with pointer jumping.

Independent of the program under test: numpy over the benchmark's own edge
list. Every vertex ends labelled with the smallest vertex id of its
component. A round takes, for every vertex, the minimum label over itself
and its neighbours, then jumps every label to its label's label until no
label moves. Rounds repeat until one changes nothing.
"""
from __future__ import annotations

import numpy as np


def _neighbour_min(lab: np.ndarray, src: np.ndarray, dst: np.ndarray,
                   starts: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """min(lab[u], min over edges (u, v) of lab[v]); ``src`` sorted."""
    out = lab.copy()
    if len(src):
        out[rows] = np.minimum(out[rows],
                               np.minimum.reduceat(lab[dst], starts))
    return out


def _jump(lab: np.ndarray) -> np.ndarray:
    while True:
        nxt = lab[lab]
        if np.array_equal(nxt, lab):
            return lab
        lab = nxt


def rounds(n: int, src: np.ndarray, dst: np.ndarray):
    """Yield the labels after each round that changed something; the last
    one yielded is the answer."""
    if len(src) > 1 and np.any(src[1:] < src[:-1]):
        order = np.argsort(src, kind="stable")
        src, dst = src[order], dst[order]
    starts = np.flatnonzero(np.r_[True, src[1:] != src[:-1]]) \
        if len(src) else np.zeros(0, np.int64)
    rows = src[starts]
    lab = np.arange(n, dtype=np.int64)
    while True:
        nxt = _jump(_neighbour_min(lab, src, dst, starts, rows))
        if np.array_equal(nxt, lab):
            return
        lab = nxt
        yield lab


def components(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """(n,) smallest vertex id of each vertex's component."""
    lab = np.arange(n, dtype=np.int64)
    for lab in rounds(n, src, dst):
        pass
    return lab


def components_one_round_short(n: int, src: np.ndarray,
                               dst: np.ndarray) -> np.ndarray:
    """The control: the same rounds with the last one that changed a label
    left out, as a loop stopped one superstep early would leave them."""
    prev = lab = np.arange(n, dtype=np.int64)
    for nxt in rounds(n, src, dst):
        prev, lab = lab, nxt
    return prev
