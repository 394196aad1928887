"""Benchmark harness entry point — one function per paper table.

    PYTHONPATH=src python -m benchmarks.run [--scale N] [--full] [--csv out]

Default scale is CPU-friendly (~8k vertices / ~100k edges per graph);
--full uses 4x larger graphs. Emits the per-table results as text plus a
final CSV block.
"""
from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from repro import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--csv", default=None)
    ap.add_argument("--tables", default="4,5a,5b,5c,6,7,sssp,fusion",
                    help="comma list: 4,5a,5b,5c,6,7,sssp,fusion")
    args = ap.parse_args()

    scale = args.scale or (15 if args.full else 13)

    from benchmarks import common, tables

    todo = set(args.tables.split(","))
    if "4" in todo:
        tables.table4_basic_channels(scale)
    if "5a" in todo:
        tables.table5_scatter_combine(scale)
    if "5b" in todo:
        tables.table5_request_respond(scale)
    if "5c" in todo:
        tables.table5_propagation(scale)
    if "6" in todo:
        tables.table6_sv_composition(scale)
    if "7" in todo:
        tables.table7_minlabel_scc(scale - 1)
    if "sssp" in todo:
        tables.bonus_sssp(scale - 1)
    if "fusion" in todo:
        from benchmarks import superstep_fusion
        print()
        superstep_fusion.run_and_write(scale + 1)

    stats = tables.session_stats()
    hit_rate = stats["cache_hits"] / max(stats["runs"], 1)
    print("\nengine session (compile-once across tables):", stats,
          f"(per-run cache hits: {stats['cache_hits']}/{stats['runs']}"
          f" = {hit_rate:.0%})")

    print("\n== CSV ==")
    common.print_csv()
    if args.csv:
        with open(args.csv, "w") as f:
            common.print_csv(f)


if __name__ == "__main__":
    main()
