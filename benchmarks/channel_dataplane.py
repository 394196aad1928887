"""Data-plane microbenchmark: sort-route vs bucket-route, and the
reference vs Pallas segment-combine.

    PYTHONPATH=src python -m benchmarks.channel_dataplane \
        [--scales 10 11 12 13 14 | --scale 10] [--out f]

The paper's thesis is that channel choice governs communication cost;
beneath every *dynamic* channel (DirectMessage / CombinedMessage /
RequestRespond) sits one routed exchange, so its constant factor
multiplies into every superstep of every unoptimized program. This
benchmark times exactly that primitive on the social dataset stand-in:

  - ``route``: one full routed exchange (slot computation + pack + tiled
    all_to_all, ids + one f32 payload) under both implementations —
    ``sort`` (the legacy stable-argsort baseline) and ``bucket`` (the
    one-pass counting data plane, jnp reference path on CPU). Both
    produce bit-identical ``Routed`` results (pinned by
    tests/test_dataplane.py), so this is a pure constant-factor race.
  - ``combine``: the scatter-combine hot loop (sorted-segment reduction
    over one worker's edge array) via the jnp reference vs the Pallas
    kernel with the plan's autotuned block sizes. On CPU the kernel runs
    in interpret mode — a correctness vehicle, recorded for the record,
    not a race it can win; on TPU it is the default path, compiled for
    the chip (both kernels compile for v5e: tests/test_tpu_compile.py).

Results go to ``BENCH_channel_dataplane.json``; the ``headline`` block
records the bucket-vs-sort speedup at the largest benched scale (the
acceptance bar is >= 1.5x on the host backend).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro import compile_cache
from repro.core import routing
from repro.core.channel import ChannelContext
from repro.kernels import ops as kops
from repro.kernels import ref as kref

AXIS = "w"
W = common.W


def _time(fn, *args, repeats=5):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def bench_route(scale: int, repeats: int):
    """One routed exchange over the raw edge lists of the social graph."""
    pg = common.partitioned("social", scale, "random", ("raw_out",))
    raw = pg.raw_out
    m = raw.e_cap
    payload = {"v": jnp.ones((W, m), jnp.float32)}
    cap = m  # ample capacity: the race is the permutation, not overflow

    def exchange(impl):
        def shard(dst, valid, pay):
            ctx = ChannelContext(AXIS, W, pg.n_loc)
            routed = routing.route(ctx, dst, valid, pay, cap, impl=impl)
            return routed.ids, routed.payload, routed.sent_count

        return jax.jit(jax.vmap(shard, axis_name=AXIS))

    row = {"m_per_worker": int(m)}
    for impl in ("sort", "bucket"):
        t = _time(exchange(impl), raw.dst_global, raw.mask, payload,
                  repeats=repeats)
        row[f"{impl}_s"] = round(t, 6)
        print(f"  scale {scale:2d} route/{impl:7s} M={m:6d} {t*1e3:9.3f} ms")
    row["speedup"] = round(row["sort_s"] / row["bucket_s"], 3)
    print(f"  scale {scale:2d} route speedup (sort/bucket) "
          f"{row['speedup']:.2f}x")
    return row


def bench_combine(scale: int, repeats: int):
    """The sorted-segment combine on one worker's edge array: reference
    vs the Pallas kernel under the plan's autotuned block sizes."""
    pg = common.partitioned("social", scale, "random", ("scatter_out",))
    plan = pg.scatter_out
    seg = plan.edge_seg[0]
    rng = np.random.default_rng(scale)
    vals = jnp.asarray(rng.normal(size=(plan.e_cap, 1)).astype(np.float32))

    ref_fn = jax.jit(lambda v, s: kref.segment_combine_ref(
        v, s, plan.u_cap, "sum"))
    work_list = (plan.item_block[0], plan.item_chunk[0])
    kern_fn = jax.jit(lambda v, s: kops.segment_combine(
        v, s, plan.u_cap, "sum", use_kernel=True, assume_sorted=True,
        block_rows=plan.block_rows, block_edges=plan.block_edges,
        work_list=work_list))

    t_ref = _time(ref_fn, vals, seg, repeats=repeats)
    t_kern = _time(kern_fn, vals, seg, repeats=repeats)
    np.testing.assert_allclose(np.asarray(kern_fn(vals, seg)),
                               np.asarray(ref_fn(vals, seg)),
                               rtol=1e-4, atol=1e-4)
    print(f"  scale {scale:2d} combine ref {t_ref*1e3:9.3f} ms   kernel"
          f"({'interpret' if kops.resolve_interpret() else 'tpu'}) "
          f"{t_kern*1e3:9.3f} ms")
    return {
        "edges": int(plan.e_cap),
        "segments": int(plan.u_cap),
        "block_rows": int(plan.block_rows),
        "block_edges": int(plan.block_edges),
        "ref_s": round(t_ref, 6),
        "kernel_s": round(t_kern, 6),
        "kernel_interpret": kops.resolve_interpret(),
    }


def run(scales, repeats: int = 5):
    out = {
        "workers": W,
        "dataset": "social",
        "scales": list(scales),
        "use_kernel_default": kops.resolve_use_kernel(),
        "route_impl_default": routing.resolve_impl(),
        "route": {},
        "combine": {},
        "headline": {},
    }
    for scale in scales:
        out["route"][str(scale)] = bench_route(scale, repeats)
        out["combine"][str(scale)] = bench_combine(scale, repeats)
    largest = str(max(scales))
    out["headline"] = {
        "largest_scale": int(largest),
        "route_speedup": out["route"][largest]["speedup"],
        "target": 1.5,
    }
    print(f"== headline: bucket-route {out['headline']['route_speedup']}x "
          f"faster than sort-route at scale {largest} ==")
    return out


def run_and_write(scales, repeats: int = 5,
                  out_path: str = "BENCH_channel_dataplane.json"):
    print(f"== Channel data plane (social, scales {list(scales)}) ==")
    out = run(scales, repeats)
    out["provenance"] = common.provenance()
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    return out


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scales", type=int, nargs="+",
                    default=[10, 11, 12, 13, 14])
    ap.add_argument("--scale", type=int, default=None,
                    help="single-scale shorthand (tier-1 smoke)")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--out", default="BENCH_channel_dataplane.json")
    args = ap.parse_args()
    scales = [args.scale] if args.scale is not None else args.scales
    run_and_write(scales, args.repeats, args.out)


if __name__ == "__main__":
    main()
