"""Routed-channel batching benchmark: the union-frontier route pass vs
the per-lane baseline vs a serial per-query loop.

    PYTHONPATH=src python -m benchmarks.routed_batching [--scale 12]
        [--queries 32] [--out BENCH_routed_batching.json]

``benchmarks/query_throughput.py`` measured the PR-5 moral: batching
paid off only where the channel plan is *static* — the dynamically
routed channels (CombinedMessage dedup + wire packing, RequestRespond)
re-paid their route pass per query lane and landed below 1x. This
benchmark measures the fix: with ``route_batch="union"`` every routed
channel computes the union frontier across the Q lanes each superstep
and runs ONE shared bucket-route pass, with payloads riding as
``(slots, Q)`` lane matrices.

Three executions of the same program through warm ``Engine`` sessions
(never a compile inside a timed region):

  - serial: Q ``run_batch(prog, pg, [s])`` calls — one compiled Q=1
    executable replayed per query;
  - lane:   ``Engine(route_batch="lane")`` — the PR-5 baseline, the
    query vmap batches Q independent route passes;
  - union:  ``Engine(route_batch="union")`` — one shared route pass.

Per-query outputs are asserted bit-identical across all three before
anything is timed. Results (queries/sec per program plus the
``headline`` union-vs-serial speedup, target >= 3x for sssp:basic at
scale 12 / Q=32) go to ``BENCH_routed_batching.json``;
``scripts/tier1.sh`` (full mode) runs a small smoke of this benchmark
and schema-checks the artifact.
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
HEADLINE_PROGRAM = "sssp:basic"
TARGET = 3.0
# every query-parametric program whose channels are dynamically routed
DEFAULT_KEYS = ("sssp:basic", "reach:basic", "pj:reqresp")


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _bench_program(key: str, scale: int, q: int, repeats: int):
    spec = REGISTRY[key]
    graph = spec.make_graph(scale, 0)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    queries = spec.queries(graph, 0, q)
    q = len(queries)  # queries() clamps to graph.n — rate by actual Q
    prog = spec.factory(**spec.inputs(graph, 0))
    engines = {
        "serial": Engine(mode="fused", route_batch="union"),
        "lane": Engine(mode="fused", route_batch="lane"),
        "union": Engine(mode="fused", route_batch="union"),
    }

    # warm every executable and pin bit-identity before timing anything
    res_u = engines["union"].run_batch(prog, pg, queries)
    res_l = engines["lane"].run_batch(prog, pg, queries)
    serial = [engines["serial"].run_batch(prog, pg, [s]) for s in queries]
    for qi in range(q):
        want = np.asarray(serial[qi].outputs[0])
        np.testing.assert_array_equal(np.asarray(res_u.outputs[qi]), want)
        np.testing.assert_array_equal(np.asarray(res_l.outputs[qi]), want)
        assert int(res_u.query_steps[qi]) == int(serial[qi].query_steps[0])
        assert res_u.query_bytes(qi) == serial[qi].query_bytes(0)

    t = {
        "serial": min(_timed(lambda: [engines["serial"].run_batch(
            prog, pg, [s]) for s in queries]) for _ in range(repeats)),
        "lane": min(_timed(lambda: engines["lane"].run_batch(
            prog, pg, queries)) for _ in range(repeats)),
        "union": min(_timed(lambda: engines["union"].run_batch(
            prog, pg, queries)) for _ in range(repeats)),
    }
    row = {
        "graph_n": graph.n,
        "q": q,
        "channel_class": spec.channel_class,
        "supersteps_batched": int(res_u.steps),
        "wall_s": t,
        "queries_per_s": {k: q / v for k, v in t.items()},
        "speedup_union": t["serial"] / t["union"],
        "speedup_lane": t["serial"] / t["lane"],
        "union_vs_lane": t["lane"] / t["union"],
        "outputs_match": True,
    }
    print(f"  {key:14s} serial {q / t['serial']:8.1f} q/s   "
          f"lane {q / t['lane']:8.1f} q/s   "
          f"union {q / t['union']:8.1f} q/s   "
          f"union speedup {row['speedup_union']:6.2f}x "
          f"(vs lane {row['union_vs_lane']:.2f}x)")
    return row


def run(scale: int = 12, q: int = 32, repeats: int = 3, keys=DEFAULT_KEYS):
    out = {"scale": scale, "workers": W, "q": q, "repeats": repeats,
           "mode": "fused", "programs": {}}
    for key in keys:
        out["programs"][key] = _bench_program(key, scale, q, repeats)
    head_key = (HEADLINE_PROGRAM if HEADLINE_PROGRAM in out["programs"]
                else next(iter(out["programs"])))
    head = out["programs"][head_key]
    out["headline"] = {
        "program": head_key,
        "scale": scale,
        "q": q,
        "queries_per_s_serial": head["queries_per_s"]["serial"],
        "queries_per_s_union": head["queries_per_s"]["union"],
        "speedup_union": head["speedup_union"],
        "speedup_lane": head["speedup_lane"],
        "union_vs_lane": head["union_vs_lane"],
        "target": TARGET,
        "meets_target": head["speedup_union"] >= TARGET,
    }
    print(f"  headline: {head_key} {head['speedup_union']:.2f}x "
          f"union-vs-serial (target {TARGET}x) at scale {scale}, Q={q}")
    return out


def run_and_write(scale: int = 12, q: int = 32, repeats: int = 3,
                  keys=DEFAULT_KEYS,
                  out_path: str = "BENCH_routed_batching.json"):
    print(f"== Routed-channel batching (scale {scale}, W={W}, Q={q}) ==")
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
                    help="comma list of routed batched registry keys")
    ap.add_argument("--out", default="BENCH_routed_batching.json")
    args = ap.parse_args()
    run_and_write(args.scale, args.queries, args.repeats,
                  tuple(args.keys.split(",")), args.out)


if __name__ == "__main__":
    main()
