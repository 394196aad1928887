"""Traffic kind ``jobs``: whole-graph analytics jobs of the mix's
``program``, back to back, one at a time, through ``Engine.run``. The
window runs jobs until its time is up; the job that straddles the close is
finished and counted. A jobs mix has no random input."""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

import drive


class Driver:
    def __init__(self, traffic: dict, config: dict, graph, seed: int):
        self.engine = drive.Engine(**config["engine"])
        self.prog = drive.get_program(traffic["program"])

    def warm_up(self, pg) -> float:
        """One job: compiles (or loads) the cell's only program."""
        res = self.engine.run(self.prog, pg)
        return res.compile_time_s

    def measure(self, pg, seconds: float) -> drive.Window:
        jobs = []
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("bench/job"):
                start = time.perf_counter() - t0
                res = self.engine.run(self.prog, pg)
                end = time.perf_counter() - t0
            jobs.append(drive.Job(start, end, res.steps, res.total_bytes,
                                  res.halted, np.asarray(res.output)))
            del res
            if end >= seconds:
                return drive.Window(end, jobs=jobs)
