#!/usr/bin/env python3
"""Smoke test of the main path on TPU: kernels, analytics and serving.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the shard_map mesh path only

One process drives every phase through the same public entry points as
``python -m repro run`` / ``serve``: ``repro.algorithms.REGISTRY``,
``pgraph.partition_graph`` and ``Engine``. Graphs are generated from
``--seed``; nothing outside the committed tree is read. Any phase that
fails raises, and the script exits non-zero without a result line.

One chip (default):
  (a) device    — the first JAX device must be a TPU;
  (b) kernels   — bucket_ranks, bucket_ranks_lanes and segment_combine
                  lowered for the chip (``use_kernel`` on, interpret off)
                  at graph sizes, equal to ``repro.kernels.ref``;
  (c) analytics — ``sv:composed`` (W=8, random partition, fused loop)
                  checked against the host oracle, then re-run from the
                  engine's compile cache;
  (d) service   — ``Engine.serve`` of ``reach:basic`` queries through 8
                  lanes, every answer bit-identical to a solo run and to
                  the host BFS oracle.

``--chips 4``: ``sv:composed`` and ``Engine.serve`` (``reach:basic``) on
a 4-device ``shard_map`` mesh, degree partitioner with hub mirroring,
bit-identical to ``backend="vmap"`` with W=4 on one device.

The last line printed is the JSON result,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import types

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.algorithms import REGISTRY  # noqa: E402
from repro.graph import pgraph  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.pregel.engine import Engine  # noqa: E402
from repro.pregel.serve import QueryQueue  # noqa: E402


#: graph scales: sv:composed at 2^22 vertices (about 1.6 GB of partitioned
#: graph on the host); the reach:basic service at 2^20, the largest scale
#: whose 8-lane serving loop fits one v5e's 16 GB (2^22 needs ~54 GB)
SV_SCALE = 22
REACH_SCALE = 20
QUERIES = 32
#: --chips 4 checks the mesh path, not the size: four chips cost four times
#: the chip time, and at 22/20 the one-device vmap twins alone take minutes
MESH_SV_SCALE = 20
MESH_REACH_SCALE = 18
#: kernel-phase sizes: one worker's messages and sorted edges/segments of
#: a scale-22 graph (n_loc = 2^19 at W=8, ~8 edges per vertex)
KERNEL_MSGS = 1 << 20
KERNEL_EDGES = 1 << 22
KERNEL_SEGMENTS = 1 << 19


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def exact(got, want, what: str) -> None:
    """Bit-for-bit equality of two pytrees of arrays."""
    g_leaves = jax.tree_util.tree_leaves(got)
    w_leaves = jax.tree_util.tree_leaves(want)
    check(len(g_leaves) == len(w_leaves), f"{what}: structure differs")
    for g, w in zip(g_leaves, w_leaves):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=what)


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SmokeFailure(f"no TPU: jax.devices()[0].platform is "
                           f"{d0.platform!r}")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, found {len(devices)}")
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# (b) kernels at graph sizes
# ---------------------------------------------------------------------------


def phase_kernels(seed: int) -> None:
    check(kops.resolve_use_kernel(), "kernels are off on this backend")
    check(not kops.resolve_interpret(), "kernels would run interpreted")
    log("[kernels] use_kernel=True interpret=False")
    rng = np.random.default_rng(seed)

    # routing: M messages over W=8 owners (+ the invalid sentinel), and
    # the Q=8 lane membership of the batched data plane
    w, m, q = 8, KERNEL_MSGS, 8
    keys = jnp.asarray(rng.integers(0, w + 1, m), jnp.int32)
    lanes = jnp.asarray(rng.random((m, q)) < 0.5) & (keys < w)[:, None]
    for name, kern, ref_fn, args in (
            ("bucket_ranks", kops.bucket_ranks, kref.bucket_ranks_ref,
             (keys,)),
            ("bucket_ranks_lanes Q=8", kops.bucket_ranks_lanes,
             kref.bucket_ranks_lanes_ref, (keys, lanes))):
        got, dt = timed(jax.jit(lambda *a, _f=kern: _f(*a, w)), *args)
        exact(got, jax.jit(lambda *a, _f=ref_fn: _f(*a, w))(*args), name)
        log(f"[kernels] {name:24s} M={m} B={w}: equal to ref "
            f"(first call {dt:.3f}s)")

    # scatter-combine: E sorted edges into N segments, with the host
    # work list a ScatterPlan carries. The f32 sum adds small integers,
    # which is exact in any order, so it too must match bit for bit.
    n, e = KERNEL_SEGMENTS, KERNEL_EDGES
    seg_np = np.sort(rng.integers(0, n, e)).astype(np.int32)
    br, be = kops.autotune_block_sizes(n, e)
    seg = jnp.asarray(seg_np)
    plan = tuple(map(jnp.asarray, kops.build_work_list(
        *kops.plan_chunks(seg_np, n, br, be))))
    cases = (
        ("segment_combine int32 min", "min",
         rng.integers(0, n, (e, 1)).astype(np.int32)),
        ("segment_combine f32 sum", "sum",
         rng.integers(0, 16, (e, 1)).astype(np.float32)),
        ("segment_combine int32 min D=8", "min",
         rng.integers(0, n, (e, 8)).astype(np.int32)),
    )
    for name, comb, vals_np in cases:
        vals = jnp.asarray(vals_np)
        kern = jax.jit(lambda v, s, _c=comb: kops.segment_combine(
            v, s, n, _c, assume_sorted=True, block_rows=br,
            block_edges=be, work_list=plan))
        got, dt = timed(kern, vals, seg)
        want = jax.jit(lambda v, s, _c=comb: kref.segment_combine_ref(
            v, s, n, _c))(vals, seg)
        exact(got, want, name)
        log(f"[kernels] {name:30s} E={e} N={n} D={vals_np.shape[1]} "
            f"blocks=({br},{be}): equal to ref (first call {dt:.3f}s)")


# ---------------------------------------------------------------------------
# (c) analytics and (d) service on one chip
# ---------------------------------------------------------------------------


def build(key: str, scale: int, workers: int, seed: int,
          partitioner: str = "random", mirror_threshold=None):
    spec = REGISTRY[key]
    t0 = time.perf_counter()
    graph = spec.make_graph(scale, seed)
    pg = pgraph.partition_graph(graph, workers, partitioner,
                                build=spec.build,
                                mirror_threshold=mirror_threshold)
    log(f"[{key}] scale {scale}: n={graph.n} edges={graph.num_edges} "
        f"W={workers} (host build {time.perf_counter() - t0:.1f}s)")
    return spec, graph, pg, spec.make(graph, seed)


def run_line(res) -> str:
    return (f"steps={res.steps} traffic={res.total_bytes} B "
            f"msgs={res.total_msgs} wall={res.wall_time_s:.3f}s "
            f"compile={res.compile_time_s:.2f}s "
            f"cache={'hit' if res.cache_hit else 'miss'} "
            f"use_kernel={res.use_kernel}")


def phase_analytics(scale: int, seed: int) -> None:
    spec, graph, pg, prog = build("sv:composed", scale, 8, seed)
    eng = Engine(mode="fused")
    first = eng.run(prog, pg)
    log(f"[sv:composed] run 0: {run_line(first)}")
    check(first.use_kernel, "sv:composed compiled without the kernels")
    check(first.halted, "sv:composed did not reach its fixpoint")
    t0 = time.perf_counter()
    spec.check(graph, pg, first, spec.inputs(graph, seed))
    log(f"[sv:composed] oracle: ok ({time.perf_counter() - t0:.1f}s)")
    again = eng.run(prog, pg)
    log(f"[sv:composed] run 1: {run_line(again)}")
    check(again.cache_hit and eng.compiles == 1,
          f"second run recompiled: {eng.stats()}")
    check(again.steps == first.steps
          and again.bytes_by_channel == first.bytes_by_channel,
          "second run differs from the first")
    exact(again.output, first.output, "sv:composed rerun output")
    for name in sorted(first.bytes_by_channel):
        log(f"[sv:composed]   {name:32s} {first.bytes_by_channel[name]:12d} "
            f"B {first.msgs_by_channel[name]:10d} msgs")


def serve(eng: Engine, spec, graph, pg, prog, queries: int, seed: int):
    schedule = spec.stream(graph, seed, queries, rate=1.0)
    res = eng.serve(prog, pg, QueryQueue.from_schedule(schedule),
                    num_lanes=8)
    check(res.num_queries == queries and not res.failed_qids,
          f"served {res.num_queries}/{queries}, failed {res.failed_qids}")
    return res


def phase_service(scale: int, queries: int, seed: int) -> None:
    spec, graph, pg, prog = build("reach:basic", scale, 8, seed)
    eng = Engine(mode="chunked", chunk_size=4)
    res = serve(eng, spec, graph, pg, prog, queries, seed)
    lat = res.latency_summary()
    log(f"[reach:basic serve] {res.num_queries} queries / {res.num_lanes} "
        f"lanes: dispatches={res.dispatches} supersteps={res.supersteps} "
        f"wall={res.wall_time_s:.3f}s compile={res.compile_time_s:.2f}s "
        f"p50={lat['p50_steps']:.0f} p99={lat['p99_steps']:.0f} steps")
    t0 = time.perf_counter()
    for rec in res.records:
        solo = eng.run_batch(prog, pg, [rec.query])
        exact(rec.output, solo.outputs[0], f"query {rec.qid} vs solo")
        check(rec.steps == int(solo.query_steps[0])
              and rec.bytes_by_channel == solo.query_bytes(0)
              and rec.msgs_by_channel == solo.query_msgs(0),
              f"query {rec.qid}: steps/traffic differ from its solo run")
        spec.check(graph, pg, types.SimpleNamespace(output=rec.output),
                   {"source": rec.query})
    log(f"[reach:basic serve] all {res.num_queries} served answers "
        f"bit-identical to solo run_batch and equal to the BFS oracle "
        f"({time.perf_counter() - t0:.1f}s)")


# ---------------------------------------------------------------------------
# --chips 4: shard_map mesh vs vmap on one device
# ---------------------------------------------------------------------------


def phase_mesh(sv_scale: int, reach_scale: int, queries: int,
               seed: int) -> None:
    w = 4
    mesh = jax.make_mesh((w,), ("workers",))
    part = dict(partitioner="degree", mirror_threshold="auto")

    spec, graph, pg, prog = build("sv:composed", sv_scale, w, seed, **part)
    log(f"[mesh sv:composed] mirrored hub slots per worker: "
        f"{pg.scatter_out.hub_cap}")
    r_v = Engine(backend="vmap", mode="fused").run(prog, pg)
    log(f"[mesh sv:composed] vmap      {run_line(r_v)}")
    r_s = Engine(backend="shard_map", mesh=mesh, mode="fused").run(prog, pg)
    log(f"[mesh sv:composed] shard_map {run_line(r_s)}")
    spec.check(graph, pg, r_s, spec.inputs(graph, seed))
    check((r_s.steps, r_s.halted) == (r_v.steps, r_v.halted),
          "steps differ between shard_map and vmap")
    check(r_s.bytes_by_channel == r_v.bytes_by_channel
          and r_s.msgs_by_channel == r_v.msgs_by_channel,
          "per-channel traffic differs between shard_map and vmap")
    exact(r_s.state, r_v.state, "sv:composed state")
    exact(r_s.output, r_v.output, "sv:composed output")
    log("[mesh sv:composed] oracle: ok; state, output, steps and "
        "per-channel bytes bit-identical to vmap")
    del r_v, r_s, graph, pg

    spec, graph, pg, prog = build("reach:basic", reach_scale, w, seed, **part)
    s_v = serve(Engine(backend="vmap", mode="chunked", chunk_size=4),
                spec, graph, pg, prog, queries, seed)
    s_s = serve(Engine(backend="shard_map", mesh=mesh, mode="chunked",
                       chunk_size=4), spec, graph, pg, prog, queries, seed)
    log(f"[mesh reach:basic serve] vmap wall={s_v.wall_time_s:.3f}s, "
        f"shard_map wall={s_s.wall_time_s:.3f}s, "
        f"dispatches={s_s.dispatches}")
    for rv, rs in zip(s_v.records, s_s.records):
        check((rs.qid, rs.lane, rs.admitted, rs.finished, rs.steps)
              == (rv.qid, rv.lane, rv.admitted, rv.finished, rv.steps),
              f"query {rs.qid}: schedule differs between backends")
        check(rs.bytes_by_channel == rv.bytes_by_channel
              and rs.msgs_by_channel == rv.msgs_by_channel,
              f"query {rs.qid}: traffic differs between backends")
        exact(rs.output, rv.output, f"query {rs.qid} shard_map vs vmap")
        spec.check(graph, pg, types.SimpleNamespace(output=rs.output),
                   {"source": rs.query})
    log(f"[mesh reach:basic serve] all {s_s.num_queries} served answers "
        "bit-identical to vmap and equal to the BFS oracle")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard_map mesh path")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated graphs, queries and data")
    args = ap.parse_args(argv)

    cache_dir = compile_cache.enable()
    t0 = time.perf_counter()
    device = phase_device(args.chips)
    log(f"[device] compile cache: {cache_dir}")
    if args.chips == 4:
        phase_mesh(MESH_SV_SCALE, MESH_REACH_SCALE, QUERIES, args.seed)
    else:
        phase_kernels(args.seed)
        phase_analytics(SV_SCALE, args.seed)
        phase_service(REACH_SCALE, QUERIES, args.seed)
    log(f"[done] {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
