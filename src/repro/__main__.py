"""``python -m repro`` — the registry-driven CLI.

Subcommands:

  list    every registered program (``algorithm:variant``), its declared
          channels and the graph plans it needs.
  run     run one program on a generated problem instance, verify it
          against the host oracle, and print the RunResult summary.
          ``--repeat N`` reuses the Engine session, so repeats report
          compile-cache hits instead of paying the trace again.
  bench   run a set of programs through one compile-once Engine per mode
          and print paper-style rows (supersteps / messages / bytes /
          wall time), optionally writing JSON.
  bench-batch
          the batched query plane: run every query-parametric program
          (or ``--keys``) over Q queries, once through one batched
          ``Engine.run_batch`` loop and once as a serial per-query loop,
          verify per-query outputs are bit-identical, and print
          queries/sec for both plus the speedup.
  serve   the continuous-batching query service: stream Q queries of one
          program through ``Engine.serve`` under a seeded Poisson
          arrival schedule, verify every served output bit-identical to
          a solo run, and print sustained queries/sec plus p50/p99
          latency. ``--smoke`` is the <60s CI configuration.
  plan    the channel planner: fingerprint each program on its problem
          graph, lower the declared channels to a concrete Plan, and
          print the per-knob decision table (``--explain``) with the
          predicted vs measured cost of every candidate.

Examples:

  python -m repro list
  python -m repro run wcc --scale 9
  python -m repro run sv:composed --scale 10 --mode fused --repeat 2
  python -m repro run wcc --scale 10 --plan auto
  python -m repro bench --scale 10 --keys wcc:basic,wcc:switch --json out.json
  python -m repro bench-batch --scale 10 --queries 16
  python -m repro serve reach:basic --scale 10 --queries 32 --lanes 8
  python -m repro serve --smoke
  python -m repro plan --explain
  python -m repro plan sssp:basic --scale 11 --queries 16 --explain
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro import compile_cache
from repro.algorithms import (ALGORITHMS, BATCHED, DEFAULT_VARIANT, REGISTRY,
                              resolve)
from repro.graph import partition as partition_lib
from repro.graph import pgraph
from repro.pregel.engine import Engine


def _fmt_bytes(b: int) -> str:
    return f"{b / 1e6:.3f} MB" if b >= 1e6 else f"{b} B"


def _summary(res) -> str:
    cache = "hit" if res.cache_hit else f"compile {res.compile_time_s:.2f}s"
    return (f"steps {res.steps:5d}  msgs {res.total_msgs:10d}  "
            f"traffic {_fmt_bytes(res.total_bytes):>12s}  "
            f"wall {res.wall_time_s:7.3f}s  mode {res.mode}  "
            f"dispatches {res.dispatches}  [{cache}]")


def _knob_line(plan) -> str:
    """The resolved knob set a run actually compiled under."""
    return (f"knobs: mode={plan.mode} chunk={plan.chunk_size} "
            f"use_kernel={plan.use_kernel} route_impl={plan.route_impl} "
            f"route_batch={plan.route_batch} "
            f"dense_threshold={plan.dense_threshold} [plan: {plan.source}]")


def _prepare(spec, args):
    graph = spec.make_graph(args.scale, args.seed)
    thr = getattr(args, "mirror_threshold", None)
    if thr is not None and thr != "auto":
        thr = int(thr)
    pg = pgraph.partition_graph(graph, args.workers, args.partitioner,
                                build=spec.build, mirror_threshold=thr)
    # --max-steps is a per-run Engine override (prop/pagerank factories
    # manage their own budgets), not a factory knob
    inputs = spec.inputs(graph, args.seed)
    return graph, pg, inputs, spec.make(graph, args.seed)


def cmd_list(args) -> int:
    if args.json:
        out = {
            k: {
                "algorithm": s.algorithm,
                "variant": s.variant,
                "default": DEFAULT_VARIANT[s.algorithm] == s.variant,
                "build": list(s.build),
                "channel_class": s.channel_class,
                "channels": list(s.make(s.make_graph(6, 0)).channel_names()),
            }
            for k, s in sorted(REGISTRY.items())
        }
        print(json.dumps(out, indent=2))
        return 0
    print(f"{len(REGISTRY)} registered programs "
          f"({len(ALGORITHMS)} algorithms):\n")
    for algo in ALGORITHMS:
        for key, spec in sorted(REGISTRY.items()):
            if spec.algorithm != algo:
                continue
            star = "*" if DEFAULT_VARIANT[algo] == spec.variant else " "
            plans = ",".join(spec.build) or "-"
            print(f"  {star} {key:22s} [{spec.channel_class:6s}] "
                  f"plans: {plans}")
    print("\n(* = default variant for `python -m repro run <algorithm>`)")
    return 0


def cmd_run(args) -> int:
    spec = resolve(args.program)
    mode = args.mode
    if mode is None and (args.checkpoint_every or args.resume):
        mode = "chunked"    # checkpointing snapshots the chunked carry
    if mode is None:
        mode = None if args.plan == "auto" else "fused"
    shown_mode = mode or "auto"
    print(f"== {spec.key} (scale {args.scale}, W={args.workers}, "
          f"{args.partitioner} partition, mode {shown_mode}) ==")
    graph, pg, inputs, prog = _prepare(spec, args)
    print(f"graph: n={graph.n} edges={graph.num_edges}  program: {prog}")
    eng = Engine(mode=mode, chunk_size=args.chunk_size, plan=args.plan,
                 on_overflow=args.on_overflow)
    resume = args.resume
    if resume:
        import os
        if os.path.isdir(resume):
            from repro.pregel import checkpoint as ckpt_io
            resume = ckpt_io.latest(resume)
        if resume is None or not os.path.exists(resume):
            print(f"run: no checkpoint at {args.resume}")
            return 2
        print(f"resuming from {resume}")
    res = None
    for i in range(max(1, args.repeat)):
        res = eng.run(prog, pg, max_steps=args.max_steps,
                      checkpoint_every=args.checkpoint_every,
                      checkpoint_dir=args.checkpoint_dir,
                      resume=resume)
        if i == 0:
            print(_knob_line(res.plan))
        print(f"run {i}: {_summary(res)}")
        if res.resumed_from:
            print(f"  resumed at superstep {res.resumed_from}")
        if res.recovery:
            for ev in res.recovery:
                print(f"  recovered: overflow of {list(ev['channels'])} at "
                      f"superstep {ev['superstep']} -> cap_scales "
                      f"{ev['cap_scales']}")
    if args.repeat > 1:
        print(f"engine session: {eng.stats()}")
    for name in sorted(res.bytes_by_channel):
        print(f"  {name:32s} {res.bytes_by_channel[name]:12d} B "
              f"{res.msgs_by_channel[name]:10d} msgs")
    if args.check and spec.check is not None:
        spec.check(graph, pg, res, inputs)
        print("oracle: ok")
    return 0


def cmd_bench(args) -> int:
    keys = (args.keys.split(",") if args.keys
            else [f"{a}:{DEFAULT_VARIANT[a]}" for a in ALGORITHMS])
    modes = args.modes.split(",")
    engines = {m: Engine(mode=m, chunk_size=args.chunk_size,
                         plan=args.plan) for m in modes}
    rows = []
    shown = set()
    print(f"== bench (scale {args.scale}, W={args.workers}) ==")
    for name in keys:
        spec = resolve(name)
        graph, pg, inputs, prog = _prepare(spec, args)
        for mode in modes:
            res = engines[mode].run(prog, pg, max_steps=args.max_steps)
            if res.plan.key() not in shown:
                shown.add(res.plan.key())
                print(f"  {_knob_line(res.plan)}")
            rows.append({
                "program": spec.key, "mode": mode, "supersteps": res.steps,
                "messages": res.total_msgs, "bytes": res.total_bytes,
                "wall_time_s": round(res.wall_time_s, 4),
                "compile_time_s": round(res.compile_time_s, 4),
                "cache_hit": res.cache_hit,
                "plan": res.plan.to_json(),
            })
            print(f"  {spec.key:22s} [{mode:7s}] {_summary(res)}")
    stats = {m: engines[m].stats() for m in modes}
    print(f"engine sessions: {stats}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"scale": args.scale, "workers": args.workers,
                       "rows": rows, "engines": stats}, f, indent=2)
        print(f"wrote {args.json}")
    return 0


def cmd_bench_batch(args) -> int:
    import numpy as np

    named = args.programs or args.keys
    keys = named.split(",") if named else list(BATCHED)
    q = args.queries
    print(f"== bench-batch (scale {args.scale}, W={args.workers}, Q={q}, "
          f"mode {args.mode}) ==")
    rows = []
    for name in keys:
        spec = resolve(name)
        if spec.make_queries is None:
            print(f"  {spec.key:22s} (no query axis — skipped)")
            continue
        if args.channel_class != "all" \
                and spec.channel_class != args.channel_class:
            continue
        graph, pg, inputs, prog = _prepare(spec, args)
        queries = spec.queries(graph, args.seed, q)
        eng = Engine(mode=args.mode, chunk_size=args.chunk_size,
                     route_batch=args.route_batch)
        batched = lambda: eng.run_batch(prog, pg, queries,
                                        max_steps=args.max_steps)
        one = lambda s: eng.run_batch(prog, pg, [s],
                                      max_steps=args.max_steps)
        # warm both executables, then verify the batch against the
        # serial loop query by query before timing anything
        res_b = batched()
        serial = [one(s) for s in queries]
        for qi in range(len(queries)):
            np.testing.assert_array_equal(
                np.asarray(res_b.outputs[qi]),
                np.asarray(serial[qi].outputs[0]))
        t0 = time.perf_counter()
        batched()
        t_batched = time.perf_counter() - t0
        t0 = time.perf_counter()
        for s in queries:
            one(s)
        t_serial = time.perf_counter() - t0
        row = {"program": spec.key, "q": len(queries),
               "channel_class": spec.channel_class,
               "route_batch": eng.route_batch,
               "supersteps": res_b.steps,
               "queries_per_s_serial": len(queries) / t_serial,
               "queries_per_s_batched": len(queries) / t_batched,
               "speedup": t_serial / t_batched,
               "bytes": res_b.total_bytes}
        rows.append(row)
        print(f"  {spec.key:22s} [{spec.channel_class:6s}] "
              f"steps {res_b.steps:4d}  "
              f"serial {row['queries_per_s_serial']:8.1f} q/s  "
              f"batched {row['queries_per_s_batched']:8.1f} q/s  "
              f"speedup {row['speedup']:6.2f}x  [outputs bit-identical]")
    # speedup by channel class: static-plan channels batch through the
    # query vmap alone; routed channels additionally share the
    # union-frontier route pass (route_batch="union")
    by_class = {}
    for row in rows:
        by_class.setdefault(row["channel_class"], []).append(row["speedup"])
    for cls in sorted(by_class):
        sp = by_class[cls]
        geo = float(np.exp(np.mean(np.log(sp))))
        print(f"  -- {cls:6s} ({len(sp)} programs): "
              f"geomean speedup {geo:6.2f}x  "
              f"(min {min(sp):.2f}x, max {max(sp):.2f}x)")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"scale": args.scale, "workers": args.workers,
                       "q": q, "mode": args.mode,
                       "route_batch": args.route_batch or "union",
                       "rows": rows}, f, indent=2)
        print(f"wrote {args.json}")
    return 0


def cmd_serve(args) -> int:
    import numpy as np

    from repro.pregel.serve import QueryQueue

    if args.smoke:
        # the <60s CI stage: small scale, forced refills, full
        # bit-identity verification
        args.program = args.program or "reach:basic"
        args.scale = 8
        args.workers = 4
        args.queries = 12
        args.lanes = 3
        args.chunk_size = 3
    if args.program is None:
        print("serve: a program key is required (or use --smoke)")
        return 2
    spec = resolve(args.program)
    if spec.make_queries is None:
        print(f"serve: {spec.key} has no query axis")
        return 2
    chunk = args.serve_chunk if args.serve_chunk else (args.chunk_size or 64)
    print(f"== serve {spec.key} (scale {args.scale}, W={args.workers}, "
          f"Q={args.queries}, lanes={args.lanes}, chunk={chunk}, "
          f"rate={args.rate}/step) ==")
    graph, pg, inputs, prog = _prepare(spec, args)
    schedule = spec.stream(graph, args.seed, args.queries, args.rate)
    eng = Engine(mode="chunked", chunk_size=chunk,
                 route_batch=args.route_batch)
    res = eng.serve(prog, pg, QueryQueue.from_schedule(schedule),
                    num_lanes=args.lanes, max_steps=args.max_steps)
    lat = res.latency_summary()
    print(f"served {res.num_queries} queries through {res.num_lanes} lanes: "
          f"{res.dispatches} dispatches, {res.supersteps} supersteps "
          f"(clock {res.clock}), wall {res.wall_time_s:.3f}s "
          f"[{'hit' if res.cache_hit else f'compile {res.compile_time_s:.2f}s'}]")
    print(f"  sustained {res.queries_per_s:8.1f} q/s   latency p50 "
          f"{lat['p50_steps']:.0f} / p99 {lat['p99_steps']:.0f} steps "
          f"({lat['p50_wall_s'] * 1e3:.1f} / {lat['p99_wall_s'] * 1e3:.1f} ms)")
    if args.check:
        # every served answer must be bit-identical to a solo run of the
        # same query (Q=1 run_batch — itself pinned to Engine.run by the
        # tier-1 suite)
        for rec in res.records:
            solo = eng.run_batch(prog, pg, [rec.query],
                                 max_steps=args.max_steps)
            np.testing.assert_array_equal(np.asarray(rec.output),
                                          np.asarray(solo.outputs[0]))
            assert rec.steps == int(solo.query_steps[0]), \
                (rec.qid, rec.steps, int(solo.query_steps[0]))
            assert rec.bytes_by_channel == solo.query_bytes(0), rec.qid
            assert rec.msgs_by_channel == solo.query_msgs(0), rec.qid
        print(f"  bit-identity: all {res.num_queries} served outputs, step "
              "counts and traffic match solo runs")
    return 0


def cmd_plan(args) -> int:
    from repro.plan import Planner

    keys = (args.programs.split(",") if isinstance(args.programs, str)
            else args.programs) or ["wcc:switch", "sssp:basic"]
    planner = Planner(calibrate=not args.no_calibrate)
    print(f"== plan (scale {args.scale}, W={args.workers}, "
          f"Q={args.queries}) ==")
    for name in keys:
        spec = resolve(name)
        graph, pg, inputs, prog = _prepare(spec, args)
        plan = planner.plan(prog, pg, num_queries=args.queries)
        print(f"\n{spec.key}  (n={graph.n}, edges={graph.num_edges}, "
              f"class={spec.channel_class})")
        if args.explain:
            print(plan.explain())
        else:
            print(_knob_line(plan))
    if not args.no_calibrate:
        from repro.plan import cost_model
        print(f"\ncalibration cache: {cost_model.cache_dir()}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_list = sub.add_parser("list", help="list registered programs")
    p_list.add_argument("--json", action="store_true")
    p_list.set_defaults(fn=cmd_list)

    def common(p):
        p.add_argument("--scale", type=int, default=10,
                       help="graph scale (n = 2^scale)")
        p.add_argument("--workers", type=int, default=8)
        p.add_argument("--partitioner", default="random",
                       choices=sorted(partition_lib.PARTITIONERS))
        p.add_argument("--mirror-threshold", default=None,
                       help="hub-mirroring degree threshold for the "
                            "scatter/prop plans: an int, 'auto', or unset "
                            "(off). See docs/scaling.md.")
        p.add_argument("--chunk-size", type=int, default=None,
                       help="chunked-mode dispatch width (default 64; "
                            "None lets --plan auto choose)")
        p.add_argument("--max-steps", type=int, default=None)
        p.add_argument("--seed", type=int, default=0)

    p_run = sub.add_parser("run", help="run one program, verify the oracle")
    p_run.add_argument("program",
                       help="algorithm (default variant) or algorithm:variant")
    common(p_run)
    p_run.add_argument("--mode", default=None,
                       choices=("host", "fused", "chunked"),
                       help="execution mode (default: fused, or the "
                            "planner's choice under --plan auto)")
    p_run.add_argument("--plan", default="manual",
                       choices=("manual", "auto"),
                       help="knob source: manual = flags/env/defaults, "
                            "auto = the cost-model planner (explicit "
                            "flags still win)")
    p_run.add_argument("--repeat", type=int, default=1,
                       help="re-run through the same Engine session")
    p_run.add_argument("--no-check", dest="check", action="store_false",
                       help="skip the host-oracle verification")
    p_run.add_argument("--on-overflow", default="raise",
                       choices=("raise", "escalate"),
                       help="channel-capacity overflow policy: escalate "
                            "re-buckets the overflowed caps and replays")
    p_run.add_argument("--checkpoint-every", type=int, default=None,
                       help="snapshot the run every K supersteps "
                            "(chunked mode; needs --checkpoint-dir)")
    p_run.add_argument("--checkpoint-dir", default=None,
                       help="directory checkpoints are written into")
    p_run.add_argument("--resume", default=None,
                       help="checkpoint file (or directory: newest is "
                            "taken) to resume from — bit-identical to "
                            "the uninterrupted run")
    p_run.set_defaults(fn=cmd_run)

    p_bench = sub.add_parser("bench", help="bench programs via one Engine")
    p_bench.add_argument("--keys", default=None,
                         help="comma list of programs (default: one per "
                              "algorithm)")
    common(p_bench)
    p_bench.add_argument("--modes", default="fused",
                         help="comma list of execution modes")
    p_bench.add_argument("--plan", default="manual",
                         choices=("manual", "auto"),
                         help="knob source (auto = cost-model planner; "
                              "the per-engine --modes stay explicit)")
    p_bench.add_argument("--json", default=None, help="write rows to JSON")
    p_bench.set_defaults(fn=cmd_bench)

    p_bb = sub.add_parser(
        "bench-batch",
        help="batched query plane: run_batch vs a serial per-query loop")
    p_bb.add_argument("--keys", default=None,
                      help="comma list of batched programs "
                           "(default: every query-parametric program)")
    p_bb.add_argument("--programs", default=None,
                      help="alias for --keys (takes precedence)")
    common(p_bb)
    p_bb.add_argument("--mode", default="fused",
                      choices=("host", "fused", "chunked"))
    p_bb.add_argument("--channel-class", default="all",
                      choices=("static", "routed", "all"),
                      help="only bench programs of this data-plane family")
    p_bb.add_argument("--route-batch", default=None,
                      choices=("union", "lane"),
                      help="routed-channel batching strategy "
                           "(default: union, see REPRO_ROUTE_BATCH)")
    p_bb.add_argument("--queries", type=int, default=16,
                      help="batch size Q")
    p_bb.add_argument("--json", default=None, help="write rows to JSON")
    p_bb.set_defaults(fn=cmd_bench_batch)

    p_sv = sub.add_parser(
        "serve",
        help="continuous-batching query service under a Poisson workload")
    p_sv.add_argument("program", nargs="?", default=None,
                      help="a query-parametric program "
                           "(algorithm or algorithm:variant)")
    common(p_sv)
    p_sv.add_argument("--queries", type=int, default=32,
                      help="number of queries in the arrival stream")
    p_sv.add_argument("--lanes", type=int, default=8,
                      help="always-on query lanes (the batch width)")
    p_sv.add_argument("--serve-chunk", type=int, default=None,
                      help="supersteps per dispatch = admission "
                           "granularity (default: --chunk-size)")
    p_sv.add_argument("--rate", type=float, default=1.0,
                      help="Poisson arrival rate (queries per superstep)")
    p_sv.add_argument("--route-batch", default=None,
                      choices=("union", "lane"))
    p_sv.add_argument("--no-check", dest="check", action="store_false",
                      help="skip the per-query bit-identity verification")
    p_sv.add_argument("--smoke", action="store_true",
                      help="the <60s CI configuration (small scale, "
                           "forced refills, full verification)")
    p_sv.set_defaults(fn=cmd_serve)

    p_plan = sub.add_parser(
        "plan",
        help="lower programs' channels to concrete Plans (decision table)")
    p_plan.add_argument("programs", nargs="*", default=None,
                        help="programs to plan (default: wcc:switch, "
                             "sssp:basic)")
    common(p_plan)
    p_plan.add_argument("--queries", type=int, default=0,
                        help="plan for a Q-query batch (0 = single run)")
    p_plan.add_argument("--explain", action="store_true",
                        help="print the full per-knob decision table "
                             "(candidates, predicted vs measured cost)")
    p_plan.add_argument("--no-calibrate", action="store_true",
                        help="skip the timed calibration probes — corpus "
                             "fits and defaults only")
    p_plan.set_defaults(fn=cmd_plan)

    args = ap.parse_args(argv)
    compile_cache.enable()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
