"""The comparison that decides ``correct``: what the timed window produced,
against a plain reference in ``reference/`` on the same graph.

A traffic mix names its check, ``checks/<check>.py``, which lists the
programs it can judge (``PROGRAMS``) and defines ``judge(window, graph) ->
[Check]``; a mix whose program its check does not list is refused before
the run (``manifest.py``). Every number compared so far counts vertices on
which the program and the reference disagree, so every limit is 0.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

#: the program's hop count for a vertex the source cannot reach
UNREACHED = np.iinfo(np.int32).max


@dataclasses.dataclass
class Check:
    name: str
    value: int
    limit: int = 0

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def canonical(labels: np.ndarray) -> np.ndarray:
    """(n,) the smallest vertex id that shares each vertex's label."""
    labels = np.asarray(labels)
    _, inv = np.unique(labels, return_inverse=True)
    least = np.full(inv.max(initial=0) + 1, len(labels), np.int64)
    np.minimum.at(least, inv, np.arange(len(labels)))
    return least[inv]


def label_mismatch(labels: np.ndarray, reference: np.ndarray) -> int:
    """Vertices whose component differs; ``reference`` is canonical."""
    return int(np.count_nonzero(canonical(labels) != reference))


def hop_mismatch(hops: np.ndarray, reference: np.ndarray) -> int:
    want = np.where(reference < 0, UNREACHED, reference)
    return int(np.count_nonzero(np.asarray(hops, np.int64) != want))


def as_dict(checks: List[Check]) -> Dict[str, Dict[str, int]]:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}
