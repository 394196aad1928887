"""Paper-style composition tables: unoptimized vs composed, host vs fused.

    PYTHONPATH=src python -m benchmarks.paper_tables [--scale N] [--out f]

Reproduces the shape of the paper's evaluation tables (§V, Tables IV-VII)
with the composition layer as the subject: for each algorithm, the
*unoptimized* (standard-channel / Pregel-style) program against the
*composed* (optimized-channel-stack) program, under both the ``host``
and ``fused`` execution modes. Rows record supersteps (global rounds),
remote messages, remote bytes, and wall time; the S-V pair is the
paper's headline §V case study — the composed program must win on BOTH
global rounds and traffic bytes, and the emitted JSON
(``BENCH_paper_tables.json``) records that check under ``"headline"``.

The whole table is driven by the program registry
(``repro.algorithms.REGISTRY``) through one compile-once
``repro.pregel.engine.Engine`` per execution mode: each (program, shape)
is compiled at most once per mode, a warm re-run of the composed S-V
demonstrates the session cache (``"engine"`` in the JSON records the
compile/cache-hit counters), and there is no per-algorithm glue — a row
is just (label, registry key, knobs).

Wall times on CPU-sized graphs are dominated by per-superstep dispatch,
which is what the fused column shows; traffic and round counts are exact
and scale-invariant (the channels count logical remote bytes, as the
paper's tables do).
"""
from __future__ import annotations

import argparse
import json

from benchmarks import common
from repro import compile_cache
from repro.algorithms import REGISTRY
from repro.graph import pgraph
from repro.pregel.engine import Engine

MODES = ("host", "fused")

# (algorithm row label, paper dataset, [(program label, registry key,
# factory knobs)]). The composed S-V also reports per-component bytes.
CASES = (
    ("S-V", "social",
     (("unoptimized", "sv:basic", {}), ("composed", "sv:composed", {}))),
    ("WCC", "social",
     (("unoptimized", "wcc:basic", {}), ("composed", "wcc:switch", {}))),
    ("PR", "web",
     (("unoptimized", "pagerank:basic", {"iters": 10}),
      ("composed", "pagerank:scatter", {"iters": 10}))),
    ("PJ", "tree",
     (("unoptimized", "pj:basic", {}), ("composed", "pj:reqresp", {}))),
    ("MSF", "weighted",
     (("unoptimized", "msf:monolithic", {}),
      ("composed", "msf:channels", {}))),
)


def _instance(spec, dataset: str, scale: int):
    """Problem instance for a row: the paper stand-in datasets for the
    graph algorithms, the spec's own generator for the forest (PJ)."""
    if dataset == "tree":
        graph = spec.make_graph(scale, 0)
        pg = pgraph.partition_graph(graph, common.W, "random",
                                    build=spec.build)
    else:
        s = max(scale - 2, 6) if spec.algorithm == "msf" else scale
        graph = common.dataset(dataset, s)
        pg = common.partitioned(dataset, s, "random", spec.build)
    return graph, pg, spec.inputs(graph, 0)


def _row(algorithm, dataset, mode, program, res, **extra):
    row = {
        "algorithm": algorithm,
        "dataset": dataset,
        "mode": mode,
        "program": program,
        "variant": res.program,
        "supersteps": res.steps,
        "messages": res.total_msgs,
        "bytes": res.total_bytes,
        "wall_time_s": round(res.wall_time_s, 4),
        "runtime_s": round(common.adjusted_runtime(res), 4),
        "dispatches": res.dispatches,
        "compile_time_s": round(res.compile_time_s, 4),
        "cache_hit": res.cache_hit,
    }
    row.update(extra)
    print(f"  {algorithm:4s} {program:12s} [{mode:5s}] "
          f"rounds {res.steps:4d}  msgs {res.total_msgs:9d}  "
          f"bytes {res.total_bytes:11d}  wall {res.wall_time_s:7.3f}s")
    return row


def run(scale: int):
    engines = {m: Engine(mode=m) for m in MODES}
    rows = []
    sv_stats = {}
    progs = {}

    pg_by_algorithm = {}
    for algorithm, dataset, programs in CASES:
        # one problem instance per case — shared by every (mode, program)
        graph, pg, inputs = _instance(REGISTRY[programs[0][1]], dataset,
                                      scale)
        pg_by_algorithm[algorithm] = pg
        for mode in MODES:
            for label, key, knobs in programs:
                spec = REGISTRY[key]
                # one program instance per (key, knobs) across both modes
                if key not in progs:
                    progs[key] = spec.factory(**inputs, **knobs)
                res = engines[mode].run(progs[key], pg)
                extra = {}
                if key == "sv:composed":
                    extra["bytes_by_component"] = {
                        k: res.bytes_under(f"sv/{k}")
                        for k in ("pointer", "neighbor_min", "merge", "jump")
                    }
                rows.append(_row(algorithm, dataset, mode, label, res,
                                 **extra))
                if algorithm == "S-V":
                    sv_stats[(mode, label)] = res

    # --- session cache demo: warm re-run of the composed S-V -------------
    warm = engines["fused"].run(progs["sv:composed"], pg_by_algorithm["S-V"])
    assert warm.cache_hit, "same program+shape must reuse the compile"
    engine_stats = {m: engines[m].stats() for m in MODES}
    engine_stats["warm_rerun"] = {
        "program": warm.program,
        "cache_hit": warm.cache_hit,
        "wall_time_s": round(warm.wall_time_s, 4),
        "cold_wall_time_s": sv_stats[("fused", "composed")].wall_time_s,
        "cold_compile_time_s": round(
            sv_stats[("fused", "composed")].compile_time_s, 4),
    }
    print(f"\nengine sessions: {engine_stats}")

    # --- headline check: composed S-V beats unoptimized S-V ---------------
    basic = sv_stats[("fused", "unoptimized")]
    comp = sv_stats[("fused", "composed")]
    headline = {
        "algorithm": "S-V",
        "unoptimized_supersteps": basic.steps,
        "composed_supersteps": comp.steps,
        "unoptimized_bytes": basic.total_bytes,
        "composed_bytes": comp.total_bytes,
        "round_reduction": round(basic.steps / max(comp.steps, 1), 3),
        "traffic_reduction": round(
            basic.total_bytes / max(comp.total_bytes, 1), 3),
        "composed_beats_unoptimized_rounds": comp.steps < basic.steps,
        "composed_beats_unoptimized_bytes":
            comp.total_bytes < basic.total_bytes,
    }
    print(f"headline: composed S-V {headline['round_reduction']}x fewer "
          f"global rounds, {headline['traffic_reduction']}x less traffic "
          f"than unoptimized")
    return rows, headline, engine_stats


def run_and_write(scale: int, out_path: str = "BENCH_paper_tables.json"):
    print(f"== Paper composition tables (scale {scale}, W={common.W}) ==")
    rows, headline, engine_stats = run(scale)
    out = {"scale": scale, "workers": common.W, "rows": rows,
           "headline": headline, "engine": engine_stats,
           "provenance": common.provenance()}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    if not (headline["composed_beats_unoptimized_rounds"]
            and headline["composed_beats_unoptimized_bytes"]):
        raise SystemExit(
            "headline regression: composed S-V did not beat the "
            "unoptimized S-V on rounds and bytes"
        )
    return out


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--out", default="BENCH_paper_tables.json")
    args = ap.parse_args()
    run_and_write(args.scale, args.out)


if __name__ == "__main__":
    main()
