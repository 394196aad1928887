#!/usr/bin/env python3
"""Runs one benchmark cell once on the chip and prints its result line.

    python3 benchmarks/chip/run.py --workload cc-sv --seed 7 --seconds 10 --trace 0

Set-up (counted in ``setup_s``): start JAX, load the configuration's
graph (made by the generator it names from the configuration's own seed
on a cell's first run in this checkout, then read from ``.graphs/``),
partition it (``pgraph.partition_graph``), and run one warm-up job or
serving session, which compiles the cell's program or loads it from JAX's
persistent cache in ``<checkout>/.jax_cache``. Then the window:
``--seconds`` of the cell's traffic, made by the generator of its kind
(``drive.py``) and ordered by ``--seed``. After it the device's peak
memory is read, the program's answers are compared with a plain reference
by the mix's check (``check.py``), and the result is printed. Every piece
is found by its name in ``BENCHMARK.json`` (``manifest.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window and reports its per-layer metrics, each read by
``metrics/<name>.py``. The last lines of standard error, and the last key
of the result line, give each number compared with its limit.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: generated graphs, kept in the checkout after a cell's first run
GRAPHS = HERE / ".graphs"
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
# the TPU runtime's logs go under this run's TMPDIR, not a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(chips: int) -> list:
    """The first ``chips`` TPU devices: those the cell runs on."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


class GcTally:
    """Python's garbage collections while installed: count, longest and
    total seconds, on the host clock."""

    def __init__(self):
        self.count, self.longest, self.total = 0, 0.0, 0.0
        self._start = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
            return
        took = time.perf_counter() - self._start
        self.count += 1
        self.longest = max(self.longest, took)
        self.total += took


def main(argv=None, devices=None, manifest_path=None) -> int:
    """``devices`` and ``manifest_path`` stand in for the chip and for
    ``BENCHMARK.json`` in the harness's own tests."""
    args = parse(argv)
    import manifest

    m = manifest.Manifest.load(manifest_path)
    cell = m.cell(args.workload)
    config = m.config(cell["config"])
    traffic = m.traffic(cell["traffic"])
    generator, kind, judge = m.generator(config), m.kind(traffic), \
        m.check(traffic)
    if devices is None:
        devices = find_chips(cell["chips"])

    import jax
    import numpy as np

    from repro import compile_cache
    from repro.algorithms import REGISTRY
    from repro.graph import pgraph
    from repro.graph.generators import EdgeList

    import check
    import graphs
    import trace

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    graph = graphs.cached(config["graph"], GRAPHS, generator.make)
    edges = EdgeList(graph.n, np.stack([graph.src, graph.dst], 1)
                     .astype(np.int64), None, directed=False,
                     name=config["name"])
    t = time.perf_counter()
    pg = pgraph.partition_graph(edges, config["workers"],
                                config["partitioner"],
                                seed=config["partition_seed"],
                                build=REGISTRY[traffic["program"]].build)
    partition_s = time.perf_counter() - t
    del edges
    load = kind.Driver(traffic, config, graph, args.seed)
    compile_s = load.warm_up(pg)
    # Set-up leaves millions of objects behind (the graph build, the traced
    # programs). Collect them here, and keep the survivors out of later
    # collections, so that no full collection of them lands in the window.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START

    traces: list = []
    gc_tally = GcTally()
    gc.callbacks.append(gc_tally)
    if args.trace:
        with trace.capture(traces):
            window = load.measure(pg, args.seconds)
    else:
        window = load.measure(pg, args.seconds)
    gc.callbacks.remove(gc_tally)
    device = device_info(devices)
    del load, pg
    checks = judge.judge(window, graph)

    # everything a metric reader may read about this run
    run = types.SimpleNamespace(cell=cell, config=config, traffic=traffic, graph=graph,
              window=window, setup_s=setup_s, partition_s=partition_s,
              compile_s=compile_s, device=device,
              trace=traces[0] if traces else None, here=HERE)
    metrics = {}
    wanted = m.per_layer(cell["name"]) if args.trace \
        else m.end_to_end(cell["name"])
    for metric in wanted:
        value = m.reader(metric["name"])(run)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    if window.jobs:
        attempted = len(window.jobs)
        failed = sum(not j.halted for j in window.jobs)
    else:
        attempted = len(window.answers)
        failed = sum(a.status != "ok" for a in window.answers)
    result = {"correct": all(c.ok for c in checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.idle_gaps(10)}
    result["checks"] = check.as_dict(checks)
    print(f"[{cell['name']}] seed={args.seed} attempted={attempted} "
          f"failed={failed} window={window.elapsed_s:.3f}s "
          f"setup={setup_s:.3f}s partition={partition_s:.3f}s "
          f"compile={compile_s:.3f}s", file=sys.stderr)
    for job in window.jobs:
        print(f"job {job.start_s:.3f}-{job.end_s:.3f}s steps={job.steps} "
              f"bytes={job.total_bytes}", file=sys.stderr)
    if window.answers:
        print(f"answers {len(window.answers)} dispatches={window.dispatches} "
              f"done_s={[round(a.done_s, 3) for a in window.answers]}",
              file=sys.stderr)
    print(f"gc in window: collections={gc_tally.count} "
          f"longest={gc_tally.longest:.3f}s total={gc_tally.total:.3f}s",
          file=sys.stderr)
    for c in checks:
        print(f"check {c.name}={c.value} limit={c.limit}", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoChip as err:
        print(f"error: {err}", file=sys.stderr)
        sys.exit(2)
