"""Query-throughput benchmark: one batched multi-query loop vs a serial
per-query loop.

    PYTHONPATH=src python -m benchmarks.query_throughput [--scale 12]
        [--queries 32] [--out BENCH_query_throughput.json]

The serving question behind the ROADMAP's batching axis: given Q
independent queries of one program (Q SSSP landmark sources, Q
reachability roots, Q personalization vertices), how many queries per
second does one worker fleet answer? Two executions of the *same*
program are compared, both through one warm ``Engine`` session so no
compile time is ever inside a timed region:

  - serial:  Q ``run_batch(prog, pg, [s])`` calls — one compiled Q=1
    executable replayed per query (compile-cache hits), paying the
    per-run dispatch/readback/extract cost Q times;
  - batched: one ``run_batch(prog, pg, sources)`` call — the query axis
    is vmapped inside the superstep, so every superstep advances all Q
    queries and the per-run cost is paid once.

Per-query outputs are asserted bit-identical between the two before
anything is timed. Results (queries/sec per program plus the
``headline`` speedup, target >= 3x at scale 12 / Q=32) go to
``BENCH_query_throughput.json``; ``scripts/tier1.sh`` runs a small-Q
smoke of this benchmark and schema-checks the artifact.

What the rows show: batching pays off exactly where the channel plan is
*static* — personalized PageRank (ScatterCombine) and propagation-style
SSSP amortize their plan work across the query axis (~3-12x), while the
dynamically *routed* channels (sssp:basic / reach:basic CombinedMessage)
re-pay their per-lane dedup + wire packing per query and land below 1x.
Pick the channel with the query axis in mind — the composition-layer
moral, now measured.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro import compile_cache
from repro.algorithms import REGISTRY
from repro.graph import pgraph
from repro.pregel.engine import Engine

W = 8
HEADLINE_PROGRAM = "pagerank:personal"
TARGET = 3.0
DEFAULT_KEYS = ("sssp:basic", "sssp:prop", "reach:basic",
                "pagerank:personal")


def _bench_program(key: str, scale: int, q: int, repeats: int):
    spec = REGISTRY[key]
    graph = spec.make_graph(scale, 0)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    sources = spec.queries(graph, 0, q)
    q = len(sources)  # queries() clamps to graph.n — rate by actual Q
    prog = spec.factory(**spec.inputs(graph, 0))
    eng = Engine(mode="fused")

    # warm both executables (batch cap and the Q=1 cap) and check that
    # the batched per-query outputs are bit-identical to the serial loop
    res_b = eng.run_batch(prog, pg, sources)
    serial = [eng.run_batch(prog, pg, [s]) for s in sources]
    for qi in range(len(sources)):
        np.testing.assert_array_equal(
            np.asarray(res_b.outputs[qi]), np.asarray(serial[qi].outputs[0]))
        assert int(res_b.query_steps[qi]) == int(serial[qi].query_steps[0])

    t_batched = min(
        _timed(lambda: eng.run_batch(prog, pg, sources))
        for _ in range(repeats))
    t_serial = min(
        _timed(lambda: [eng.run_batch(prog, pg, [s]) for s in sources])
        for _ in range(repeats))

    row = {
        "graph_n": graph.n,
        "q": q,
        "supersteps_batched": int(res_b.steps),
        "wall_s_batched": t_batched,
        "wall_s_serial": t_serial,
        "queries_per_s_batched": q / t_batched,
        "queries_per_s_serial": q / t_serial,
        "speedup": t_serial / t_batched,
        "outputs_match": True,
        "engine": eng.stats(),
    }
    print(f"  {key:20s} serial {q / t_serial:8.1f} q/s   "
          f"batched {q / t_batched:8.1f} q/s   "
          f"speedup {row['speedup']:6.2f}x")
    return row


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def run(scale: int = 12, q: int = 32, repeats: int = 3,
        keys=DEFAULT_KEYS):
    out = {"scale": scale, "workers": W, "q": q, "repeats": repeats,
           "mode": "fused", "programs": {}}
    for key in keys:
        out["programs"][key] = _bench_program(key, scale, q, repeats)
    head = out["programs"].get(HEADLINE_PROGRAM,
                               next(iter(out["programs"].values())))
    out["headline"] = {
        "program": HEADLINE_PROGRAM if HEADLINE_PROGRAM in out["programs"]
        else next(iter(out["programs"])),
        "scale": scale,
        "q": q,
        "queries_per_s_serial": head["queries_per_s_serial"],
        "queries_per_s_batched": head["queries_per_s_batched"],
        "speedup": head["speedup"],
        "target": TARGET,
        "meets_target": head["speedup"] >= TARGET,
    }
    print(f"  headline: {out['headline']['program']} "
          f"{out['headline']['speedup']:.2f}x "
          f"(target {TARGET}x) at scale {scale}, Q={q}")
    return out


def run_and_write(scale: int = 12, q: int = 32, repeats: int = 3,
                  keys=DEFAULT_KEYS,
                  out_path: str = "BENCH_query_throughput.json"):
    print(f"== Query throughput (scale {scale}, W={W}, Q={q}) ==")
    out = run(scale, q, repeats, keys)
    from benchmarks import common
    out["provenance"] = common.provenance()
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    return out


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--keys", default=",".join(DEFAULT_KEYS),
                    help="comma list of batched registry keys")
    ap.add_argument("--out", default="BENCH_query_throughput.json")
    args = ap.parse_args()
    run_and_write(args.scale, args.queries, args.repeats,
                  tuple(args.keys.split(",")), args.out)


if __name__ == "__main__":
    main()
