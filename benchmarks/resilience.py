"""Resilience benchmark: the cost and fidelity of the recovery paths.

    PYTHONPATH=src python -m benchmarks.resilience [--scale 10]
        [--out BENCH_resilience.json]

Three drills, all verified bit-identical before anything is reported:

  escalation  run a program with every channel capacity halved under
              ``Engine(on_overflow="escalate")`` and measure what the
              re-bucket-and-replay recovery costs next to the untouched
              run (retries taken, recovered wall time / baseline wall
              time) — plus the memoized second run, which must take zero
              retries because the engine learned the right caps.
  checkpoint  a chunked run snapshotted every K supersteps vs the same
              run unsnapshotted (checkpoint overhead), then a resume
              from the newest mid-run snapshot (must replay the
              uninterrupted run byte for byte).
  quarantine  a serving session with deterministic fault injections on a
              subset of qids: the failed queries are quarantined, every
              healthy query must still match its solo run bit for bit,
              and the session reports the failures instead of dying.

The headline is the conjunction: all three drills recovered AND stayed
bit-identical. ``scripts/tier1.sh`` runs a small smoke of this benchmark
and schema-checks the artifact (``benchmarks.check_schema``).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro import compile_cache
from repro.algorithms import REGISTRY
from repro.graph import pgraph
from repro.pregel import checkpoint as ckpt_io
from repro.pregel.engine import Engine
from repro.pregel.serve import FaultSpec

W = 8
ESCALATE_KEY = "wcc:basic"
SERVE_KEY = "reach:basic"


def _same(a, b) -> bool:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def _problem(key: str, scale: int, seed: int = 0):
    spec = REGISTRY[key]
    graph = spec.make_graph(scale, seed)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    return graph, pg, spec.factory(**spec.inputs(graph, seed))


def bench_escalation(scale: int, seed: int = 0) -> dict:
    _, pg, prog = _problem(ESCALATE_KEY, scale, seed)
    base_eng = Engine()
    ref = base_eng.run(prog, pg)         # compile
    t0 = time.perf_counter()
    ref = base_eng.run(prog, pg)         # warm baseline
    t_base = time.perf_counter() - t0

    eng = Engine(cap_scales={"*": 0.5}, on_overflow="escalate")
    t0 = time.perf_counter()
    res = eng.run(prog, pg)              # cold: pays retries + compiles
    t_recover = time.perf_counter() - t0
    t0 = time.perf_counter()
    res2 = eng.run(prog, pg)             # memoized: right-sized start
    t_memo = time.perf_counter() - t0

    retries = len(res.recovery or [])
    return {
        "program": ESCALATE_KEY,
        "cap_scale": 0.5,
        "retries": retries,
        "recovery": [dict(ev, channels=list(ev["channels"]))
                     for ev in (res.recovery or [])],
        "retries_memoized": len(res2.recovery or []),
        "wall_baseline_s": t_base,
        "wall_recovered_s": t_recover,
        "wall_memoized_s": t_memo,
        "bit_identical": bool(
            _same(res.output, ref.output) and res.steps == ref.steps
            and res.bytes_by_channel == ref.bytes_by_channel),
        "memoized_bit_identical": bool(_same(res2.output, ref.output)),
    }


def bench_checkpoint(scale: int, ckpt_dir: str, every: int = 2,
                     seed: int = 0) -> dict:
    _, pg, prog = _problem(ESCALATE_KEY, scale, seed)
    eng = Engine(mode="chunked", chunk_size=2)
    plain = eng.run(prog, pg)            # compile + baseline
    t0 = time.perf_counter()
    plain = eng.run(prog, pg)
    t_plain = time.perf_counter() - t0

    t0 = time.perf_counter()
    full = eng.run(prog, pg, checkpoint_every=every,
                   checkpoint_dir=ckpt_dir)
    t_ckpt = time.perf_counter() - t0

    newest = ckpt_io.latest(ckpt_dir)
    ck = ckpt_io.load(newest) if newest else None
    resumed = (Engine(mode="chunked", chunk_size=2).run(
        prog, pg, resume=ck) if ck else None)
    return {
        "program": ESCALATE_KEY,
        "checkpoint_every": every,
        "steps": int(full.steps),
        "checkpoints_written": 0 if ck is None else int(ck.step // every),
        "wall_plain_s": t_plain,
        "wall_checkpointed_s": t_ckpt,
        "overhead_frac": (t_ckpt - t_plain) / t_plain if t_plain else 0.0,
        "resumed_from": 0 if resumed is None else int(resumed.resumed_from),
        "resume_bit_identical": bool(
            resumed is not None
            and _same(resumed.output, full.output)
            and resumed.steps == full.steps
            and resumed.bytes_by_channel == full.bytes_by_channel
            and resumed.msgs_by_channel == full.msgs_by_channel),
    }


def bench_quarantine(scale: int, q: int = 12, lanes: int = 4,
                     chunk: int = 2, seed: int = 0) -> dict:
    graph, pg, prog = _problem(SERVE_KEY, scale, seed)
    spec = REGISTRY[SERVE_KEY]
    queries = [int(s) for s in spec.queries(graph, seed, q)]
    faults = [FaultSpec(qid=1, at_step=1, kind="overflow"),
              FaultSpec(qid=q - 2, at_step=0, kind="overflow"),
              FaultSpec(qid=q // 2, at_step=2, kind="exhaust")]
    eng = Engine(mode="chunked", chunk_size=chunk)
    t0 = time.perf_counter()
    res = eng.serve(prog, pg, queries, num_lanes=lanes, faults=faults)
    wall = time.perf_counter() - t0

    faulted = {f.qid for f in faults}
    healthy_identical = True
    for rec in res.records:
        if rec.qid in faulted:
            continue
        solo = eng.run_batch(prog, pg, [rec.query])
        healthy_identical &= (
            _same(rec.output, solo.outputs[0])
            and rec.steps == int(solo.query_steps[0])
            and rec.bytes_by_channel == solo.query_bytes(0))
    return {
        "program": SERVE_KEY,
        "q": q,
        "lanes": lanes,
        "chunk_size": chunk,
        "faults": [{"qid": f.qid, "at_step": f.at_step, "kind": f.kind}
                   for f in faults],
        "failed_qids": list(res.failed_qids),
        "statuses": {str(r.qid): r.status for r in res.records},
        "served": int(res.num_queries),
        "wall_s": wall,
        "straggler_dispatches": list(res.straggler_dispatches),
        "dispatch_median_s": float(res.dispatch_median_s),
        "quarantine_isolated": bool(
            healthy_identical
            and res.num_queries == q
            and set(res.failed_qids)
            == {f.qid for f in faults if f.kind == "overflow"}),
    }


def run(scale: int = 10, ckpt_dir: str = None, seed: int = 0) -> dict:
    import tempfile

    if ckpt_dir is None:
        ckpt_dir = tempfile.mkdtemp(prefix="repro_ckpt_")
    print("== escalation drill ==")
    esc = bench_escalation(scale, seed)
    print(f"  {esc['program']}: {esc['retries']} retries, recovered "
          f"{esc['wall_recovered_s']:.2f}s vs baseline "
          f"{esc['wall_baseline_s']:.2f}s, memoized retries "
          f"{esc['retries_memoized']} "
          f"[bit-identical: {esc['bit_identical']}]")
    print("== checkpoint drill ==")
    ck = bench_checkpoint(scale, ckpt_dir, seed=seed)
    print(f"  {ck['program']}: {ck['steps']} steps, overhead "
          f"{ck['overhead_frac'] * 100:.1f}%, resumed from superstep "
          f"{ck['resumed_from']} [bit-identical: "
          f"{ck['resume_bit_identical']}]")
    print("== quarantine drill ==")
    qa = bench_quarantine(scale, seed=seed)
    print(f"  {qa['program']}: served {qa['served']}, failed qids "
          f"{qa['failed_qids']} [isolated: {qa['quarantine_isolated']}]")

    ok = (esc["bit_identical"] and esc["memoized_bit_identical"]
          and esc["retries_memoized"] == 0
          and ck["resume_bit_identical"] and qa["quarantine_isolated"])
    out = {
        "scale": scale,
        "workers": W,
        "seed": seed,
        "escalation": esc,
        "checkpoint": ck,
        "quarantine": qa,
        "headline": {
            "escalate_bit_identical": esc["bit_identical"],
            "resume_bit_identical": ck["resume_bit_identical"],
            "quarantine_isolated": qa["quarantine_isolated"],
            "escalation_retries": esc["retries"],
            "checkpoint_overhead_frac": ck["overhead_frac"],
            "target": "all recovery paths bit-identical",
            "meets_target": bool(ok),
        },
    }
    print(f"  headline: all drills bit-identical = {ok}")
    return out


def run_and_write(scale: int = 10, seed: int = 0,
                  out_path: str = "BENCH_resilience.json"):
    print(f"== Resilience (scale {scale}, W={W}) ==")
    out = run(scale, seed=seed)
    from benchmarks import common
    out["provenance"] = common.provenance()
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    return out


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_resilience.json")
    args = ap.parse_args()
    run_and_write(args.scale, args.seed, args.out)


if __name__ == "__main__":
    main()
