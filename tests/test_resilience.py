"""The resilient execution layer (PR 9).

Pins the three recovery contracts end to end:

  1. Cap-overflow escalation — ``Engine(on_overflow="escalate")`` turns a
     channel-capacity overflow into a bounded re-bucket-and-replay, and
     the recovered run is bit-identical to a run that had enough capacity
     from the start (swept across every registry program with globally
     halved caps).
  2. Checkpoint/resume — a chunked run snapshotted at dispatch
     boundaries and resumed from any snapshot replays the uninterrupted
     run byte for byte: states, step counts, and per-channel traffic.
  3. Serve-lane quarantine — an injected (or real) per-lane failure in a
     serving session takes out exactly that query; every healthy query
     still matches its solo run bit for bit and the failure is reported
     on the session result.

Plus the structured failure taxonomy itself (``repro.pregel.errors``)
across all three execution modes, the int32 traffic-wrap latch, and the
graph/weight input validation that keeps malformed problems from
reaching the runtime at all.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import REGISTRY, sssp
from repro.core import message as msg
from repro.graph import generators as gen, pgraph
from repro.pregel import checkpoint as ckpt_io
from repro.pregel import errors, runtime
from repro.pregel.engine import Engine
from repro.pregel.program import VertexProgram
from repro.pregel.serve import FaultSpec, QueryQueue, as_faults

SEED = 0
W = 4
MODES = ("host", "fused", "chunked")


def _assert_same_output(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# a deterministic overflow-prone program: every vertex messages vertex 0,
# so per-peer traffic ~= n_loc and a small capacity overflows on step 0
# ---------------------------------------------------------------------------

def fanin_program(capacity: int, steps: int = 3) -> VertexProgram:
    def init(pg):
        return {"acc": jnp.zeros((pg.num_workers, pg.n_loc), jnp.float32)}

    def step(ctx, gs, state, i):
        deliv = msg.direct_send(
            ctx, jnp.zeros((ctx.n_loc,), jnp.int32), gs.v_mask,
            {"x": jnp.ones((ctx.n_loc,), jnp.float32)}, capacity=capacity,
            name="fanin")
        got = jnp.where(deliv.mask, deliv.payload["x"], 0.0).sum()
        acc = state["acc"].at[0].add(got)
        return {"acc": acc}, i >= steps - 1, deliv.overflow

    return VertexProgram(
        name="test:fanin", init=init, step=step,
        extract=lambda pg, s: pg.to_global(s["acc"]),
        max_steps=steps + 2)


@functools.lru_cache(maxsize=None)
def small_pg():
    g = gen.rmat(6, edge_factor=4, seed=SEED).symmetrized()
    return pgraph.partition_graph(g, W, "random", build=("raw_out",))


# ---------------------------------------------------------------------------
# failure taxonomy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_overflow_error_is_structured_in_all_modes(mode):
    """Every mode raises ChannelOverflowError (a RuntimeError) carrying
    the superstep, the offending channel names, and the partial result."""
    pg = small_pg()
    prog = fanin_program(capacity=2)
    eng = Engine(mode=mode, chunk_size=2)
    with pytest.raises(errors.ChannelOverflowError,
                       match="capacity overflow") as ei:
        eng.run(prog, pg)
    err = ei.value
    assert isinstance(err, RuntimeError)
    assert err.superstep is not None
    assert "fanin" in err.channels
    assert err.result is not None
    assert err.result.overflow_by_channel["fanin"]


@pytest.mark.parametrize("mode", MODES)
def test_traffic_wrap_raises_in_all_modes(mode):
    """The int32 traffic accumulator wrap is a structured latch in every
    mode, not a silent corruption (fused mode cannot attribute the
    channel — the latch is global there)."""
    pg = small_pg()

    def step(ctx, gs, state, i):
        # 2^31 bytes in one superstep: the int32 stat leaf goes negative
        ctx.add_traffic("big", 2 ** 30, 1)
        ctx.add_traffic("big", 2 ** 30, 1)
        return state, False

    state0 = {"x": jnp.zeros((pg.num_workers, pg.n_loc), jnp.float32)}
    with pytest.raises(errors.TrafficWrapError):
        runtime.run_supersteps(pg, step, state0, max_steps=8, mode=mode,
                               chunk_size=2)


# ---------------------------------------------------------------------------
# overflow escalation
# ---------------------------------------------------------------------------

def test_escalate_recovers_and_matches_unconstrained_run():
    pg = small_pg()
    prog = fanin_program(capacity=2)
    ref = Engine().run(fanin_program(capacity=1024), pg)

    eng = Engine(on_overflow="escalate")
    res = eng.run(prog, pg)
    assert res.recovery, "escalation should have been recorded"
    assert all("fanin" in ev["channels"] or not ev["channels"]
               for ev in res.recovery)
    _assert_same_output(res.output, ref.output)
    assert res.steps == ref.steps
    assert not any(np.asarray(v).any()
                   for v in (res.overflow_by_channel or {}).values())


def test_escalation_is_memoized_per_fingerprint():
    """A second run of the same problem starts at the learned scales —
    no retries, and the executable the escalation compiled is warm."""
    pg = small_pg()
    prog = fanin_program(capacity=2)
    eng = Engine(on_overflow="escalate")
    first = eng.run(prog, pg)
    assert first.recovery
    compiles_after_first = eng.compiles
    second = eng.run(prog, pg)
    assert second.recovery is None
    assert second.cache_hit
    assert eng.compiles == compiles_after_first
    _assert_same_output(first.output, second.output)


def test_escalate_bounded_by_max_retries():
    """A program that overflows no matter the capacity (impossible here,
    so simulate with max_retries=0) still raises, with the recovery
    trail attached to the error's partial result."""
    pg = small_pg()
    prog = fanin_program(capacity=2)
    eng = Engine(on_overflow="escalate", max_retries=0)
    with pytest.raises(errors.ChannelOverflowError):
        eng.run(prog, pg)


@pytest.mark.slow
@pytest.mark.parametrize("key", sorted(REGISTRY))
def test_registry_sweep_halved_caps_escalate_bit_identical(key):
    """Acceptance sweep: every registry program, run with every channel
    capacity halved under ``on_overflow="escalate"``, produces output,
    step count and traffic bit-identical to the untouched run —
    whether or not the halved caps actually overflowed."""
    spec = REGISTRY[key]
    graph = spec.make_graph(6, SEED)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    prog = spec.factory(**spec.inputs(graph, SEED))

    ref = Engine().run(prog, pg)
    res = Engine(cap_scales={"*": 0.5}, on_overflow="escalate").run(prog, pg)
    _assert_same_output(res.output, ref.output)
    assert res.steps == ref.steps
    assert res.bytes_by_channel == ref.bytes_by_channel
    assert res.msgs_by_channel == ref.msgs_by_channel


# ---------------------------------------------------------------------------
# convergence reporting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_converged_flag_mode_parity(mode):
    spec = REGISTRY["wcc:basic"]
    graph = spec.make_graph(6, SEED)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    prog = spec.factory(**spec.inputs(graph, SEED))
    eng = Engine(mode=mode, chunk_size=3)
    assert eng.run(prog, pg).converged
    short = eng.run(prog, pg, max_steps=1)
    assert not short.converged and short.steps == 1


def test_on_nonconverged_policies():
    spec = REGISTRY["wcc:basic"]
    graph = spec.make_graph(6, SEED)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    prog = spec.factory(**spec.inputs(graph, SEED))

    with pytest.raises(errors.NonConvergenceError) as ei:
        Engine(on_nonconverged="raise").run(prog, pg, max_steps=1)
    assert ei.value.result is not None and ei.value.result.steps == 1

    with pytest.warns(RuntimeWarning, match="did not converge"):
        Engine(on_nonconverged="warn").run(prog, pg, max_steps=1)

    # default: silent (pagerank-style fixed-iteration budgets are normal)
    res = Engine().run(prog, pg, max_steps=1)
    assert not res.converged

    with pytest.raises(ValueError):
        Engine(on_nonconverged="explode")
    with pytest.raises(ValueError):
        Engine(on_overflow="retry")


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------

def _wcc_problem():
    spec = REGISTRY["wcc:basic"]
    graph = spec.make_graph(7, SEED)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    return pg, spec.factory(**spec.inputs(graph, SEED))


def test_checkpoint_resume_bit_identical(tmp_path):
    pg, prog = _wcc_problem()
    eng = Engine(mode="chunked", chunk_size=1)
    full = eng.run(prog, pg, checkpoint_every=1,
                   checkpoint_dir=str(tmp_path))
    ckpts = sorted(tmp_path.glob("*.ckpt"))
    assert len(ckpts) >= 2, "run too short to exercise resume"

    for path in ckpts:                # resume from every mid-run snapshot
        ck = ckpt_io.load(str(path))
        resumed = Engine(mode="chunked", chunk_size=1).run(
            prog, pg, resume=ck)
        assert resumed.resumed_from == ck.step
        assert resumed.steps == full.steps
        assert resumed.halted == full.halted
        assert resumed.converged == full.converged
        assert resumed.bytes_by_channel == full.bytes_by_channel
        assert resumed.msgs_by_channel == full.msgs_by_channel
        _assert_same_output(resumed.output, full.output)
        _assert_same_output(resumed.state, full.state)


def test_checkpoint_resume_from_path_and_latest(tmp_path):
    pg, prog = _wcc_problem()
    eng = Engine(mode="chunked", chunk_size=2)
    full = eng.run(prog, pg, checkpoint_every=2,
                   checkpoint_dir=str(tmp_path))
    newest = ckpt_io.latest(str(tmp_path))
    assert newest is not None
    resumed = Engine(mode="chunked", chunk_size=2).run(
        prog, pg, resume=newest)
    _assert_same_output(resumed.output, full.output)
    assert resumed.steps == full.steps


def test_checkpoint_validation_rejects_mismatches(tmp_path):
    pg, prog = _wcc_problem()
    Engine(mode="chunked", chunk_size=2).run(
        prog, pg, checkpoint_every=2, checkpoint_dir=str(tmp_path))
    path = ckpt_io.latest(str(tmp_path))
    ck = ckpt_io.load(path)

    other = fanin_program(capacity=1024)
    with pytest.raises(ValueError, match="program"):
        Engine(mode="chunked").run(other, small_pg(), resume=ck)
    with pytest.raises(ValueError, match="max_steps"):
        Engine(mode="chunked", chunk_size=2).run(
            prog, pg, max_steps=ck.max_steps + 1, resume=ck)
    with pytest.raises(ValueError, match="graph signature"):
        g2 = gen.rmat(6, edge_factor=4, seed=SEED + 3).symmetrized()
        pg2 = pgraph.partition_graph(g2, W, "random",
                                     build=REGISTRY["wcc:basic"].build)
        Engine(mode="chunked", chunk_size=2).run(prog, pg2, resume=ck)


def test_checkpoint_requires_chunked_and_dir(tmp_path):
    pg, prog = _wcc_problem()
    with pytest.raises(ValueError, match="chunked"):
        Engine(mode="fused").run(prog, pg, checkpoint_every=2,
                                 checkpoint_dir=str(tmp_path))
    with pytest.raises(ValueError, match="checkpoint_dir"):
        Engine(mode="chunked").run(prog, pg, checkpoint_every=2)


# ---------------------------------------------------------------------------
# serve-lane quarantine + fault injection
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def serve_problem():
    spec = REGISTRY["reach:basic"]
    graph = spec.make_graph(spec.test_scale, SEED)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    prog = spec.factory(**spec.inputs(graph, SEED))
    queries = [int(q) for q in spec.queries(graph, SEED, 8)]
    return pg, prog, queries


def test_serve_fault_injection_isolates_failures():
    pg, prog, queries = serve_problem()
    eng = Engine(mode="chunked", chunk_size=3)
    faults = [FaultSpec(qid=2, at_step=1, kind="overflow"),
              (5, 2, "exhaust")]
    res = eng.serve(prog, pg, queries, num_lanes=3, faults=faults)

    assert res.num_queries == len(queries)
    assert res.failed_qids == [2]
    by_qid = {r.qid: r for r in res.records}
    bad = by_qid[2]
    assert bad.status == "overflow" and bad.injected
    assert bad.output is None and not bad.halted
    ex = by_qid[5]
    assert ex.status == "exhausted" and ex.injected
    assert ex.output is not None and not ex.halted
    assert ex.steps >= 2

    # every un-faulted query is bit-identical to its solo run
    for rec in res.records:
        if rec.qid in (2, 5):
            continue
        solo = eng.run_batch(prog, pg, [rec.query])
        assert rec.status == "ok" and not rec.injected
        np.testing.assert_array_equal(np.asarray(rec.output),
                                      np.asarray(solo.outputs[0]))
        assert rec.steps == int(solo.query_steps[0])
        assert rec.bytes_by_channel == solo.query_bytes(0)
        assert rec.msgs_by_channel == solo.query_msgs(0)

    # session totals still equal the sum of per-record attributions
    for name, total in res.bytes_by_channel.items():
        assert total == sum(r.bytes_by_channel.get(name, 0)
                            for r in res.records), name


def test_serve_on_fault_raise_reports_qids():
    pg, prog, queries = serve_problem()
    eng = Engine(mode="chunked", chunk_size=3)
    with pytest.raises(errors.ChannelOverflowError) as ei:
        eng.serve(prog, pg, queries, num_lanes=3,
                  faults=[FaultSpec(qid=1, at_step=0)], on_fault="raise")
    assert list(ei.value.qids) == [1]


def test_serve_quarantined_lane_is_recycled():
    """A quarantined lane must keep serving later arrivals — the failed
    tenancy never leaks into the next occupant's answer."""
    pg, prog, queries = serve_problem()
    eng = Engine(mode="chunked", chunk_size=3)
    res = eng.serve(prog, pg, queries, num_lanes=2,
                    faults=[FaultSpec(qid=0, at_step=0)])
    assert res.failed_qids == [0]
    served_ok = [r for r in res.records if r.status != "overflow"]
    assert len(served_ok) == len(queries) - 1
    for rec in served_ok:
        solo = eng.run_batch(prog, pg, [rec.query])
        np.testing.assert_array_equal(np.asarray(rec.output),
                                      np.asarray(solo.outputs[0]))


def test_serve_straggler_monitor_reports():
    """The session reports the median wall time of its dispatches."""
    pg, prog, queries = serve_problem()
    res = Engine(mode="chunked", chunk_size=3).serve(
        prog, pg, queries, num_lanes=3)
    assert res.dispatches > 0
    assert 0.0 < res.dispatch_median_s <= res.wall_time_s


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultSpec(qid=0, at_step=0, kind="meteor")
    with pytest.raises(ValueError, match="at_step"):
        FaultSpec(qid=0, at_step=-1)
    with pytest.raises(ValueError, match="duplicate"):
        as_faults([(0, 1, "overflow"), (0, 2, "exhaust")])
    with pytest.raises(ValueError, match="on_fault"):
        pg, prog, queries = serve_problem()
        Engine(mode="chunked").serve(prog, pg, queries, on_fault="panic")


# ---------------------------------------------------------------------------
# input validation
# ---------------------------------------------------------------------------

def test_partition_rejects_out_of_range_endpoints():
    g = gen.EdgeList(n=8, edges=np.array([[0, 1], [2, 9]], np.int64))
    with pytest.raises(ValueError, match="outside"):
        pgraph.partition_graph(g, 2, "random", build=("raw_out",))
    g2 = gen.EdgeList(n=8, edges=np.array([[0, 1], [-1, 2]], np.int64))
    with pytest.raises(ValueError, match="outside"):
        pgraph.partition_graph(g2, 2, "random", build=("raw_out",))


def test_partition_rejects_nonfinite_weights():
    edges = np.array([[0, 1], [1, 2]], np.int64)
    for bad in (np.nan, np.inf):
        g = gen.EdgeList(n=4, edges=edges,
                         weights=np.array([1.0, bad], np.float32))
        with pytest.raises(ValueError, match="non-finite"):
            pgraph.partition_graph(g, 2, "random", build=("raw_out",))


def test_sssp_rejects_negative_weights():
    edges = np.array([[0, 1], [1, 2], [2, 3]], np.int64)
    g = gen.EdgeList(n=4, edges=edges,
                     weights=np.array([1.0, -2.0, 3.0], np.float32))
    pg = pgraph.partition_graph(g, 2, "random",
                                build=("raw_out", "prop_out"))
    for variant in sssp.VARIANTS:
        prog = sssp.program(variant=variant, source=0)
        with pytest.raises(ValueError, match="non-negative"):
            prog.init(pg)
        with pytest.raises(ValueError, match="non-negative"):
            prog.query_init(pg, 0)
