"""Weak-scaling benchmark: per-device throughput as devices x scale grow.

    PYTHONPATH=src python -m benchmarks.weak_scaling [--scale 12]
        [--devices 1,2,4] [--repeats 3] [--out BENCH_weak_scaling.json]

Weak scaling holds the per-device problem size fixed: at D devices the
R-MAT scale is ``scale + log2(D)`` (2x vertices and edges per doubling),
the mesh is a real forced-D-device CPU ``shard_map`` mesh, and W = D.

Metric honesty: the forced host devices **time-share one physical
socket**, so at D devices each device's fair share of the machine is
1/D — perfect weak scaling keeps the *aggregate* problem throughput
(edges solved per wall second, ``m / wall``) flat as problem and device
count double together, which is exactly "per-device throughput held"
once each device is granted its 1/D socket share. The headline
``per_device_ratio`` is therefore aggregate throughput at D_max divided
by the tuned single-device run's aggregate throughput; both
configurations are measured against that same single-device reference.

Two configurations per device count:

  degree+mirror  the ``degree`` partitioner with ``mirror_threshold=
                 "auto"`` hub mirroring — the tentpole path. Its output
                 is asserted bit-identical to the unmirrored run before
                 anything is reported.
  random         the degree-blind baseline: whichever worker draws the
                 R-MAT hubs carries their whole cut — its remote message
                 volume blows up with D (``msg_ratio_random`` in the
                 headline) and its efficiency lands below target.

Each device count runs in a subprocess because
``--xla_force_host_platform_device_count`` must be set before jax
initializes. The child prints its measurements as one JSON line behind
a marker; the parent aggregates, stamps provenance, and writes the
``BENCH_weak_scaling.json`` artifact (schema pinned by
``benchmarks.check_schema``; smoke-run by ``scripts/tier1.sh``).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

PROGRAM = "wcc:switch"
DATASET = "social"          # rmat ef8 symmetrized — the hubby regime
TARGET = 0.75               # efficiency at Dmax vs tuned single-device
CHILD_MARKER = "WEAK-SCALING-CHILD-JSON:"


def child(devices: int, scale: int, repeats: int, seed: int) -> None:
    """Measure one device count (runs under forced-device XLA flags)."""
    import time

    import jax
    import numpy as np

    from benchmarks import common
    from repro.algorithms import REGISTRY
    from repro.graph import pgraph
    from repro.pregel.engine import Engine

    assert jax.device_count() == devices, jax.devices()
    mesh = jax.make_mesh((devices,), ("workers",))
    spec = REGISTRY[PROGRAM]
    g = common.dataset(DATASET, scale)
    prog = spec.factory(**spec.inputs(g, seed))
    eng = Engine(backend="shard_map", mesh=mesh)

    def measure(partitioner: str, thr):
        pg = pgraph.partition_graph(
            g, devices, partitioner, build=spec.build,
            mirror_threshold=pgraph.resolve_mirror_threshold(g, thr))
        res = eng.run(prog, pg)                      # compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = eng.run(prog, pg)
            best = min(best, time.perf_counter() - t0)
        return pg, res, best

    rows = []
    pg_m, res_m, t_m = measure("degree", "auto")
    pg_0, res_0, _ = measure("degree", None)
    bit_identical = bool(
        np.array_equal(np.asarray(res_m.output), np.asarray(res_0.output))
        and res_m.steps == res_0.steps)
    pg_r, res_r, t_r = measure("random", None)

    def row(config, pg, res, wall):
        # problem throughput: edges solved per wall second. On one
        # time-shared socket this is the per-device rate times D, so a
        # flat curve = per-device throughput held at each device's 1/D
        # socket share (see module docstring). Convergence speed counts:
        # a partitioner that makes wcc take extra supersteps pays for it.
        thr = g.num_edges / wall
        return {
            "config": config, "devices": devices, "scale": scale,
            "n": g.n, "m": g.num_edges, "steps": res.steps,
            "runtime_s": round(wall, 4),
            "message_MB": round(res.total_bytes / 1e6, 4),
            "throughput": round(thr, 1),
            "throughput_per_device": round(thr / devices, 1),
            "hub_cap": pg.scatter_out.hub_cap if pg.scatter_out else 0,
            "route_cap": pg.route_cap,
        }

    rows.append(row("degree+mirror", pg_m, res_m, t_m))
    rows.append(row("random", pg_r, res_r, t_r))
    print(CHILD_MARKER + json.dumps(
        {"rows": rows, "bit_identical": bit_identical}))


def run_child(devices: int, scale: int, repeats: int, seed: int) -> dict:
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (str(root / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "benchmarks.weak_scaling", "--child",
           "--devices", str(devices), "--scale", str(scale),
           "--repeats", str(repeats), "--seed", str(seed)]
    proc = subprocess.run(cmd, env=env, cwd=str(root), text=True,
                          capture_output=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"weak_scaling child D={devices} failed:\n{proc.stdout}"
            f"\n{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith(CHILD_MARKER):
            return json.loads(line[len(CHILD_MARKER):])
    raise RuntimeError(f"weak_scaling child D={devices}: no result marker")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=12,
                    help="R-MAT scale at 1 device (+log2(D) per doubling)")
    ap.add_argument("--devices", default="1,2,4",
                    help="comma-separated device counts (powers of two)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_weak_scaling.json")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        from repro import compile_cache

        compile_cache.enable()
        child(int(args.devices), args.scale, args.repeats, args.seed)
        return 0

    devices = sorted(int(d) for d in args.devices.split(","))
    rows, bit_ok = [], True
    for d in devices:
        scale_d = args.scale + (d.bit_length() - 1)  # + log2(d)
        print(f"== D={d} scale={scale_d} ==")
        out = run_child(d, scale_d, args.repeats, args.seed)
        bit_ok &= out["bit_identical"]
        for r in out["rows"]:
            print(f"  {r['config']:14s} {r['throughput']:12.0f} edges/s "
                  f"steps {r['steps']}  {r['runtime_s']:.3f}s  "
                  f"msg {r['message_MB']:.2f} MB")
        rows.extend(out["rows"])

    def at(config: str, d: int) -> dict:
        return next(r for r in rows
                    if r["config"] == config and r["devices"] == d)

    # everything is measured against the tuned single-device run
    base = at("degree+mirror", devices[0])["throughput"]
    eff_mirror = round(at("degree+mirror", devices[-1])["throughput"] / base, 4)
    eff_random = round(at("random", devices[-1])["throughput"] / base, 4)
    mb_m = at("degree+mirror", devices[-1])["message_MB"]
    mb_r = at("random", devices[-1])["message_MB"]
    headline = {
        "program": PROGRAM, "dataset": DATASET,
        "devices_max": devices[-1],
        "per_device_ratio": eff_mirror,
        "random_ratio": eff_random,
        "msg_ratio_random": round(mb_r / mb_m, 4) if mb_m else 0.0,
        "target": TARGET,
        "meets_target": eff_mirror >= TARGET,
        "bit_identical": bit_ok,
    }
    from benchmarks import common
    data = {
        "scale": args.scale, "devices": devices, "repeats": args.repeats,
        "seed": args.seed, "program": PROGRAM, "dataset": DATASET,
        "rows": rows, "headline": headline,
        "provenance": common.provenance(),
    }
    pathlib.Path(args.out).write_text(json.dumps(data, indent=2) + "\n")
    print(f"headline: per-device ratio {headline['per_device_ratio']} "
          f"(random {headline['random_ratio']}, target >= {TARGET}) "
          f"bit_identical={bit_ok} -> {args.out}")
    return 0 if (headline["meets_target"] and bit_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
