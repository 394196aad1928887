"""Planner race: ``Engine(plan="auto")`` vs hand-set configurations.

    PYTHONPATH=src python -m benchmarks.planner \
        [--scales 10 11 12 | --scale 10] [--repeats 3] [--out f]

The planner's promise is twofold: it never loses to a careful hand-set
configuration (the knobs a maintainer who read every BENCH artifact
would pick), and it saves a careless one (plausible knobs copied from
the wrong backend — the interpreted Pallas kernel on CPU, the argsort
route baseline). This benchmark races all three over registry programs
at several scales:

  planner   Engine(plan="auto") — the cost-model decision per
            (program, graph) fingerprint
  best      the hand-tuned CPU config: reference combine, bucket route
  worst     the plausible-but-wrong config: kernel combine (interpreted
            on CPU), argsort route

and asserts, before timing anything, that every planned run's output is
bit-identical to its hand-set equivalent (same knobs, explicit) AND to
the best/worst configs — the planner only picks among proven-identical
implementations, so it can never trade correctness for speed.

Headline (largest scale): geomean over programs of t_hand / t_planner.
Targets: >= 1.0x vs best (the planner finds the good config), >= 1.3x
vs worst (it saves the bad one).
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
TARGET_VS_BEST = 1.0
TARGET_VS_WORST = 1.3
DEFAULT_KEYS = ("wcc:switch", "pagerank:scatter", "sssp:basic")

# Hand-set data-plane configs (mode/chunk left at their defaults — the
# race is about the data-plane knobs the corpus actually measures).
CONFIGS = {
    "best": dict(use_kernel=False, route_impl="bucket"),
    "worst": dict(use_kernel=True, route_impl="sort"),
}


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _bench_program(key: str, scale: int, repeats: int):
    spec = REGISTRY[key]
    graph = spec.make_graph(scale, 0)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    prog = spec.factory(**spec.inputs(graph, 0))

    planner_eng = Engine(plan="auto")
    res_p = planner_eng.run(prog, pg)
    plan = res_p.plan

    # the planned run must be bit-identical to the hand-set equivalent
    # (every plan knob passed explicitly to a manual engine) ...
    equiv = Engine(mode=plan.mode, chunk_size=plan.chunk_size,
                   use_kernel=plan.use_kernel, route_impl=plan.route_impl,
                   route_batch=plan.route_batch,
                   dense_threshold=plan.dense_threshold)
    np.testing.assert_array_equal(np.asarray(res_p.output),
                                  np.asarray(equiv.run(prog, pg).output))

    # ... and to every raced config (the planner only selects among
    # proven output-identical implementations)
    engines, times = {}, {}
    for name, cfg in CONFIGS.items():
        eng = Engine(**cfg)
        res = eng.run(prog, pg)  # warm + verify
        np.testing.assert_array_equal(np.asarray(res_p.output),
                                      np.asarray(res.output))
        engines[name] = eng

    times["planner"] = min(
        _timed(lambda: planner_eng.run(prog, pg)) for _ in range(repeats))
    for name, eng in engines.items():
        times[name] = min(
            _timed(lambda e=eng: e.run(prog, pg)) for _ in range(repeats))

    row = {
        "program": key,
        "scale": scale,
        "graph_n": graph.n,
        "supersteps": int(res_p.steps),
        "wall_s": {k: round(v, 5) for k, v in times.items()},
        "vs_best": times["best"] / times["planner"],
        "vs_worst": times["worst"] / times["planner"],
        "planner_knobs": plan.knobs(),
        "plan_source": plan.source,
        "bit_identical": True,
    }
    print(f"  {key:20s} scale {scale:2d}  "
          f"planner {times['planner'] * 1e3:8.2f}ms  "
          f"best {times['best'] * 1e3:8.2f}ms ({row['vs_best']:5.2f}x)  "
          f"worst {times['worst'] * 1e3:8.2f}ms ({row['vs_worst']:5.2f}x)"
          f"  [outputs bit-identical]")
    return row


def _geomean(xs):
    return float(np.exp(np.mean(np.log(xs))))


def run(scales, repeats: int = 3, keys=DEFAULT_KEYS):
    out = {"workers": W, "dataset": "registry defaults",
           "scales": list(scales), "repeats": repeats,
           "programs": list(keys),
           "configs": {k: dict(v) for k, v in CONFIGS.items()},
           "rows": []}
    for scale in scales:
        for key in keys:
            out["rows"].append(_bench_program(key, scale, repeats))
    top = max(scales)
    at_top = [r for r in out["rows"] if r["scale"] == top]
    geo_best = _geomean([r["vs_best"] for r in at_top])
    geo_worst = _geomean([r["vs_worst"] for r in at_top])
    out["headline"] = {
        "scale": top,
        "geomean_vs_best": round(geo_best, 3),
        "geomean_vs_worst": round(geo_worst, 3),
        "target_vs_best": TARGET_VS_BEST,
        "target_vs_worst": TARGET_VS_WORST,
        "meets_target": (geo_best >= TARGET_VS_BEST
                         and geo_worst >= TARGET_VS_WORST),
        "bit_identical": all(r["bit_identical"] for r in out["rows"]),
    }
    print(f"  headline: scale {top}  "
          f"geomean vs best {geo_best:.2f}x (target {TARGET_VS_BEST}x)  "
          f"vs worst {geo_worst:.2f}x (target {TARGET_VS_WORST}x)")
    return out


def run_and_write(scales, repeats: int = 3, keys=DEFAULT_KEYS,
                  out_path: str = "BENCH_planner.json"):
    print(f"== Planner race (scales {list(scales)}, W={W}) ==")
    out = run(scales, repeats, keys)
    from benchmarks import common
    out["provenance"] = common.provenance()
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    return out


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scales", type=int, nargs="+", default=None)
    ap.add_argument("--scale", type=int, default=None,
                    help="single-scale shorthand (the CI smoke)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--keys", default=None,
                    help="comma list of programs to race")
    ap.add_argument("--out", default="BENCH_planner.json")
    args = ap.parse_args()
    scales = args.scales or ([args.scale] if args.scale else [10, 11, 12])
    keys = tuple(args.keys.split(",")) if args.keys else DEFAULT_KEYS
    run_and_write(scales, repeats=args.repeats, keys=keys,
                  out_path=args.out)


if __name__ == "__main__":
    main()
