"""What every kind of traffic shares: the records of one measured window,
and the system's public entry points, which the kinds reach through this
module only (the program registry, ``Engine.run`` and ``Engine.serve`` with
a ``QueryQueue``).

A traffic mix is a data file, ``traffic/<mix>.json``, whose ``kind`` names
the generator that reads it: ``traffic/<kind>.py``, a module that defines
``Driver(traffic, config, graph, seed)`` with

- ``warm_up(pg) -> float``: set-up's one warm-up call, which compiles (or
  loads) the cell's programs; returns their compile seconds;
- ``measure(pg, seconds) -> Window``: the measured window.

A new kind is a new module there, found by name (``manifest.py``).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from repro.algorithms import get_program  # noqa: F401  (the kinds' way in)
from repro.pregel.engine import Engine  # noqa: F401
from repro.pregel.serve import QueryQueue  # noqa: F401


@dataclasses.dataclass
class Job:
    start_s: float           # from the window's start
    end_s: float
    steps: int
    total_bytes: int
    halted: bool
    output: np.ndarray       # (n,) labels in original vertex ids


@dataclasses.dataclass
class Answer:
    source: int
    due_s: float             # from the window's start
    done_s: float
    steps: int
    status: str
    output: Optional[np.ndarray]


@dataclasses.dataclass
class Window:
    """What one measured window produced."""

    elapsed_s: float         # from its start to the end of the last work
    jobs: List[Job] = dataclasses.field(default_factory=list)
    answers: List[Answer] = dataclasses.field(default_factory=list)
    dispatches: int = 0
    dispatch_median_s: float = 0.0
