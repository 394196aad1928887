"""Traffic kind ``closed_loop``: ``clients`` users of a query service, one
per lane, each of whom issues its next query of the mix's ``program`` the
moment its answer is harvested, through ``Engine.serve``. A query is due
when it is issued. Issuing stops when the window closes; queries in flight
are served to the end.

The queries are one fixed set, drawn from the graph's own seed: ``rounds``
rounds of one search key per client (``graphs.search_keys``), and one more
round for the warm-up session. The run's seed shuffles the keys within
each round, so it decides which client asks what; the rounds keep their
order, and are taken again from the first when all have been issued. Where
every query takes the same number of dispatches the clients move in
rounds, each as long as its deepest search, so rounds taken in another
order would end the window after another number of them.
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

import drive
import graphs


class ClosedLoopQueue(drive.QueryQueue):
    """Keeps one query ready for every free lane until the window closes.

    ``Engine.serve`` asks for a query each time a lane is free; with as
    many clients as lanes, a free lane means a client has just had its
    answer, so that client's next query is issued then. The window opens
    at the serving loop's first boundary."""

    def __init__(self, sources: np.ndarray, seconds: float):
        super().__init__()
        self.sources = sources
        self.seconds = seconds
        self.issued = 0
        self.offset: Optional[float] = None   # serving loop's clock origin
        self.start_s = 0.0                    # window start, loop clock

    def _now(self) -> float:
        return time.perf_counter() - self.offset

    def closed(self) -> bool:
        return (self.offset is not None
                and self._now() >= self.start_s + self.seconds)

    def __len__(self) -> int:
        return 0 if self.closed() else 1

    def peek_query(self):
        return int(self.sources[self.issued % len(self.sources)])

    def mark_eligible(self, now: int, wall_s: float) -> None:
        if self.offset is None:
            self.offset = time.perf_counter() - wall_s
            self.start_s = wall_s

    def next_arrival(self):
        return None if self.closed() else 0

    def pop_ready(self, now: int):
        with TraceAnnotation("bench/admit"):
            if self.closed():
                return None
            self.push(self.peek_query(), now)
            self.issued += 1
            entry = super().pop_ready(now)
            entry.wall_eligible_s = self._now()
            return entry


class Driver:
    def __init__(self, traffic: dict, config: dict, graph, seed: int):
        if traffic["clients"] != config["serve"]["lanes"]:
            raise ValueError("a closed loop needs one client per lane")
        self.lanes = config["serve"]["lanes"]
        self.engine = drive.Engine(**config["engine"])
        self.prog = drive.get_program(traffic["program"])
        pool = graphs.search_keys(graph, config["graph"]["seed"],
                                  (traffic["rounds"] + 1) * self.lanes)
        self.warm = pool[:self.lanes].tolist()
        rounds = pool[self.lanes:].reshape(traffic["rounds"], self.lanes)
        self.sources = graphs.host_rng(seed, 2).permuted(rounds, axis=1) \
            .reshape(-1)

    def warm_up(self, pg) -> float:
        """One session: compiles (or loads) the serving program and warms
        the admission and harvest paths."""
        res = self.engine.serve(self.prog, pg,
                                drive.QueryQueue.from_queries(self.warm),
                                num_lanes=self.lanes)
        return res.compile_time_s

    def measure(self, pg, seconds: float) -> drive.Window:
        queue = ClosedLoopQueue(self.sources, seconds)
        with TraceAnnotation("bench/serve"):
            res = self.engine.serve(self.prog, pg, queue,
                                    num_lanes=self.lanes)
        origin = queue.start_s
        answers = [drive.Answer(int(r.query), r.wall_eligible_s - origin,
                                r.wall_finished_s - origin, r.steps, r.status,
                                None if r.output is None
                                else np.asarray(r.output))
                   for r in res.records]
        return drive.Window(res.wall_time_s - origin, answers=answers,
                            dispatches=res.dispatches,
                            dispatch_median_s=res.dispatch_median_s)
