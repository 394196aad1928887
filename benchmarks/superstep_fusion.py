"""Dispatch-overhead benchmark: host-driven loop vs fused on-device loop.

    PYTHONPATH=src python -m benchmarks.superstep_fusion [--scale 14] [--out f]

Pointer jumping is the adversarial case for a host-driven runtime: many
cheap supersteps, so per-superstep *host overhead* — the dispatch enqueue,
the blocking halt/overflow readback and the per-step stat transfers —
rather than channel traffic governs the loop rate. The runtime instruments
exactly that cost (``RunResult.host_overhead_s``: host time spent driving
the loop, device waits excluded). The fused ``lax.while_loop`` mode pays
it once per *run* and the chunked ``lax.scan`` mode once per *chunk*,
instead of once per superstep.

The benchmark runs the same 2^scale-vertex pointer-jumping program under
all three modes and reports, per mode: per-superstep wall time and
per-superstep host overhead, plus the host-vs-fused overhead-reduction
factor. Results go to ``BENCH_superstep_fusion.json``.
"""
from __future__ import annotations

import argparse
import json
import statistics

import numpy as np

from repro import compile_cache
from repro.algorithms import pointer_jumping
from repro.graph import generators as gen, pgraph

W = 8


def _overhead_per_step(res) -> float:
    # host mode: step 0's enqueue is excluded by the runtime (compile),
    # so normalize by the steps that were actually instrumented
    denom = max(res.steps - 1, 1) if res.mode == "host" else res.steps
    return res.host_overhead_s / denom


def run(scale: int = 14, repeats: int = 5, chunk_size: int = 8):
    n = 2 ** scale
    # a parent chain maximizes supersteps (ceil(log2 depth) jumping rounds)
    par = gen.parent_chain(n, seed=1)
    empty = gen.EdgeList(n, np.zeros((0, 2), np.int64), None, True, "pj")
    pg = pgraph.partition_graph(empty, W, "random", build=())

    out = {"n": n, "workers": W, "variant": "reqresp", "repeats": repeats,
           "chunk_size": chunk_size, "modes": {}}
    for mode in ("host", "fused", "chunked"):
        per_step, ovh, steps = [], [], None
        for _ in range(repeats):
            _, res = pointer_jumping.run(pg, par, variant="reqresp",
                                         mode=mode, chunk_size=chunk_size)
            tail = res.step_times_s[1:] or res.step_times_s
            per_step.append(
                statistics.median(tail) if mode == "host"
                else res.wall_time_s / max(res.steps, 1)
            )
            ovh.append(_overhead_per_step(res))
            steps = res.steps
        out["modes"][mode] = {
            "supersteps": steps,
            "dispatches": res.dispatches,
            "per_superstep_wall_s": min(per_step),
            "host_overhead_per_superstep_s": min(ovh),
            "host_overhead_per_superstep_median_s": statistics.median(ovh),
        }
        print(f"  {mode:8s} steps {steps:3d} dispatches {res.dispatches:3d} "
              f"per-superstep {min(per_step)*1e3:8.3f} ms  "
              f"host-overhead/step {min(ovh)*1e3:7.3f} ms")

    h = out["modes"]["host"]["host_overhead_per_superstep_s"]
    f = out["modes"]["fused"]["host_overhead_per_superstep_s"]
    c = out["modes"]["chunked"]["host_overhead_per_superstep_s"]
    out["overhead_reduction_fused"] = h / f
    out["overhead_reduction_chunked"] = h / c
    print(f"  per-superstep host overhead: host/fused {h / f:7.2f}x  "
          f"host/chunked {h / c:7.2f}x")
    return out


def run_and_write(scale: int = 14, repeats: int = 5, chunk_size: int = 8,
                  out_path: str = "BENCH_superstep_fusion.json"):
    """Run the benchmark and persist its JSON artifact (single writer —
    also what benchmarks/run.py calls for the `fusion` table)."""
    print(f"== Superstep fusion (pointer jumping, n=2^{scale}) ==")
    out = run(scale, repeats, chunk_size)
    from benchmarks import common
    out["provenance"] = common.provenance()
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    return out


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--chunk-size", type=int, default=8)
    ap.add_argument("--out", default="BENCH_superstep_fusion.json")
    args = ap.parse_args()
    run_and_write(args.scale, args.repeats, args.chunk_size, args.out)


if __name__ == "__main__":
    main()
