"""The worker runtime (paper Fig. 4), SPMD-style.

A superstep is a jitted function mapped over the worker axis; channels
inside it communicate with axis-name collectives. Two interchangeable
backends execute the same step code:

  - ``vmap``: W logical workers on one device (tests/benchmarks on CPU);
  - ``shard_map``: W shards on a real mesh (the deployment path).

Orthogonally, three *execution modes* drive the superstep loop:

  - ``fused`` (default): the whole loop runs on device inside a single
    ``jax.lax.while_loop`` dispatch — halt vote, overflow latch, step
    counter and per-channel traffic all live in the loop carry. One
    host→device round-trip per *run* instead of per *superstep*.
  - ``chunked``: ``jax.lax.scan`` over ``chunk_size`` supersteps per
    dispatch; control returns to the host at chunk boundaries for stat
    streaming (int64-safe host accumulation) and max-step enforcement.
  - ``host``: the legacy Python loop — one jitted dispatch plus a
    blocking device→host readback per superstep. Kept as the baseline
    the fusion benchmark measures against.

The fused/chunked carries need a fixed-shape stats pytree, so the runtime
performs a one-time dry trace (``jax.eval_shape`` — no compute) of the
mapped step to discover the ``ChannelRegistry``: the set of channel names
and their per-step stat shapes. Programs that declare their channels
explicitly via ``channels=(...)`` skip the dry trace entirely — the
declaration *is* the registry, and ``ChannelContext.add_traffic``
validates it lazily (a channel missing from the declaration raises the
first time the step is traced for compilation).

Compilation is split from execution: :func:`compile_supersteps` builds a
:class:`CompiledSupersteps` whose executable takes the *graph as an
argument* (not a closure constant), so one compile can be replayed
across runs and across graphs with an identical shape signature — the
contract ``repro.pregel.engine.Engine`` builds its compile cache on.
:func:`run_supersteps` remains the one-shot convenience (compile, then
execute once).

Voting-to-halt: the step function returns a local halt vote; the runtime
ANDs votes across workers (psum). In fused/chunked mode the AND result
feeds the loop condition on device; in host mode it is pulled back and
checked in Python.

Batched query plane (``num_queries=Q``): the *same* step function is
vmapped over a query axis **inside** the worker mapping — state leaves
carry ``(W, Q, n_loc, ...)``, one compiled loop advances all Q query
instances (e.g. Q SSSP sources) per superstep. Halting is per query: a
``(Q,)`` halted vector lives in the carry, queries that voted halt have
their state frozen and their traffic masked to zero from the next step
on (so per-query steps/bytes/msgs are bit-identical to Q independent
runs), and the loop exits when every query has voted halt. Per-query
step counts and per-query per-channel traffic come back on the
``RunResult`` (``query_steps`` / ``query_bytes`` / ``query_msgs``).
``repro.pregel.engine.Engine.run_batch`` is the session API on top.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregator
from repro.core import compose
from repro.core import routing
from repro.core.channel import ChannelContext, ChannelRegistry, key_under
from repro.graph.pgraph import PartitionedGraph
from repro.kernels import ops as kops
from repro.pregel import errors
from repro.pregel import tracing

AXIS = "workers"


@dataclasses.dataclass
class RunResult:
    state: Any
    steps: int
    halted: bool
    bytes_by_channel: Dict[str, int]
    msgs_by_channel: Dict[str, int]
    wall_time_s: float
    step_times_s: list
    # Execution metadata (new fields default so callers constructing the
    # seed-era 7-tuple keep working).
    mode: str = "host"
    dispatches: int = 0
    compile_time_s: float = 0.0
    # Host time spent *driving* the run — dispatch enqueues, flag/stat
    # readbacks and Python bookkeeping — excluding device waits. This is
    # the per-superstep cost the fused modes amortize to once per dispatch.
    host_overhead_s: float = 0.0
    # Engine/session metadata (repro.pregel.engine): which VertexProgram
    # produced this run, its extracted output, and the state of the
    # engine's compile cache at run time. Plain run_supersteps calls leave
    # these at their defaults.
    program: str = ""
    output: Any = None
    cache_hit: bool = False
    engine_compiles: int = 0
    engine_cache_hits: int = 0
    # Data-plane configuration the loop was compiled with (resolved —
    # benchmarks report exactly which path ran): Pallas kernels vs the
    # jnp reference, the routed-exchange implementation, and (batched
    # runs) the query-batching strategy for routed channels.
    use_kernel: bool = False
    route_impl: str = ""
    route_batch: str = ""
    # The full planned configuration the Engine compiled under (a
    # repro.plan.Plan — knobs, source, fingerprint, decision records;
    # JSON via plan.to_json()). None for plain run_supersteps calls.
    plan: Any = None
    # Batched-query metadata (num_queries > 0 iff the loop carried a
    # query axis). The per-query arrays are host numpy, length Q;
    # bytes_by_channel/msgs_by_channel hold the across-query totals.
    # ``outputs`` is the per-query extracted answer list (Engine.run_batch).
    num_queries: int = 0
    query_steps: Any = None            # (Q,) int64
    query_halted: Any = None           # (Q,) bool
    query_bytes_by_channel: Optional[Dict[str, Any]] = None  # name->(Q,)
    query_msgs_by_channel: Optional[Dict[str, Any]] = None   # name->(Q,)
    outputs: Any = None
    # Pad-lane audit (batched runs): bucket-padding lanes start halted
    # (``query_live=False`` end to end), so they must never step, occupy
    # wire slots, or be charged. These aggregates over the pad lanes are
    # the evidence — all three stay zero (pinned by tests/test_batch.py).
    num_pad_lanes: int = 0
    pad_steps: int = 0
    pad_bytes: int = 0
    pad_msgs: int = 0
    # Resilience layer (repro.pregel.errors / Engine on_overflow):
    # converged distinguishes a unanimous halt vote from max_steps
    # exhaustion (for batched runs: every real lane voted halt);
    # overflow_by_channel is the per-channel overflow attribution (name ->
    # bool, or name -> (Q,) bool for batched runs); recovery is the
    # engine's escalation decision log (list of dicts, None when the run
    # needed no recovery); resumed_from is the checkpointed superstep a
    # chunked run was resumed at (0 = ran from scratch).
    converged: bool = False
    overflow_by_channel: Optional[Dict[str, Any]] = None
    recovery: Any = None
    resumed_from: int = 0

    @property
    def total_bytes(self) -> int:
        return int(sum(self.bytes_by_channel.values()))

    @property
    def total_msgs(self) -> int:
        return int(sum(self.msgs_by_channel.values()))

    # -- namespaced (composed-channel) attribution helpers ----------------

    def bytes_under(self, prefix: str) -> int:
        """Total bytes accounted under a namespaced key prefix."""
        return int(sum(v for k, v in self.bytes_by_channel.items()
                       if key_under(k, prefix)))

    def msgs_under(self, prefix: str) -> int:
        """Total messages accounted under a namespaced key prefix."""
        return int(sum(v for k, v in self.msgs_by_channel.items()
                       if key_under(k, prefix)))

    # -- per-query (batched run) views ------------------------------------

    def query_bytes(self, q: int) -> Dict[str, int]:
        """Per-channel byte totals attributed to query ``q``."""
        return {k: int(v[q]) for k, v in self.query_bytes_by_channel.items()}

    def query_msgs(self, q: int) -> Dict[str, int]:
        """Per-channel message totals attributed to query ``q``."""
        return {k: int(v[q]) for k, v in self.query_msgs_by_channel.items()}


def _scalar(x):
    """() view of a flag that may be per-worker replicated ((W,) or ())."""
    return jnp.asarray(x).reshape(-1)[0] if jnp.ndim(x) else jnp.asarray(x)


def _host_int(v) -> int:
    """Device stat leaf -> exact host int (int64-safe accumulation)."""
    return int(np.asarray(v).astype(np.int64).sum())


def scrub_graph(graph: PartitionedGraph) -> PartitionedGraph:
    """Drop the host-only static fields that carry per-graph identity but
    never enter traced code: the graph ``name``/``new_of_old`` and the
    plans' exact-count reporting statics (``total_edges`` /
    ``remote_entries`` — two graphs whose counts differ inside one
    power-of-two cap bucket must still share a treedef). Two graphs with
    identical shapes/caps scrub to identical pytree treedefs, which is
    what lets one compiled executable serve both."""

    def scatter(plan):
        # mirrored_edges is an exact count (reporting only); hub_cap and
        # route_cap are *shape* statics and stay — they change compiled
        # buffer extents, so they must split the compile cache.
        return plan if plan is None else dataclasses.replace(
            plan, remote_entries=0, total_edges=0, mirrored_edges=0)

    def prop(plan):
        return plan if plan is None else dataclasses.replace(
            plan, cut=scatter(plan.cut))

    return dataclasses.replace(
        graph, name="", new_of_old=None,
        scatter_out=scatter(graph.scatter_out),
        scatter_in=scatter(graph.scatter_in),
        prop_out=prop(graph.prop_out),
        prop_in=prop(graph.prop_in),
    )


def graph_signature(graph: PartitionedGraph):
    """Hashable shape signature of a graph: the scrubbed pytree treedef
    (all static caps/metadata) plus every leaf's shape and dtype. Equal
    signatures <=> a compiled executable is reusable (and numerically
    identical, since *all* remaining statics are part of the treedef)."""
    leaves, treedef = jax.tree_util.tree_flatten(scrub_graph(graph))
    return (treedef,
            tuple((tuple(l.shape), str(jnp.dtype(l.dtype))) for l in leaves))


def state_signature(state) -> Tuple:
    """Hashable treedef+avals signature of a state pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    return (treedef,
            tuple((tuple(jnp.shape(l)), str(jnp.result_type(l)))
                  for l in leaves))


@dataclasses.dataclass
class CompiledSupersteps:
    """A compiled superstep loop, reusable across runs.

    The wrapped executable was AOT-compiled (``jit(...).lower().compile()``)
    with the graph as an argument, so :meth:`execute` may be called many
    times — with the original graph or any graph whose
    :func:`graph_signature` matches — without ever re-tracing.
    ``repro.pregel.engine.Engine`` caches these per (program, shape, mode).
    """

    mode: str
    max_steps: int
    check_overflow: bool
    chunk_size: int
    registry: Optional[ChannelRegistry]
    compile_time_s: float
    _fn: Callable
    # resolved data-plane configuration baked into the compiled loop
    use_kernel: bool = False
    route_impl: str = "bucket"
    route_batch: str = "union"
    dense_threshold: float = 0.1
    # query-axis width the loop was lowered with (None = unbatched)
    num_queries: Optional[int] = None
    # serving substrate (compile_supersteps(serve=True)): the chunked
    # executable carries per-lane ages instead of a global step index so
    # lanes can be swapped at chunk boundaries (Engine.serve)
    serve: bool = False

    def serve_chunk(self, graph: PartitionedGraph, state, age, halted,
                    overflow):
        """One serving dispatch: advance every live lane by up to
        ``chunk_size`` supersteps. Carry: per-lane ``age`` (steps since
        admission — the step index each lane's step function sees),
        ``halted`` (lane voted halt OR lane unoccupied), ``overflow``.
        Returns ``(state, age, halted, overflow, d_steps, db, dm, dovf)``
        with ``d_steps`` the per-lane steps advanced this chunk, db/dm
        the per-step stat stream and dovf the per-step per-channel
        overflow flags (per-lane attribution for quarantine). The host (``repro.pregel.serve``) harvests
        finished lanes and refills them between calls — this method never
        re-traces, one executable serves the whole session."""
        if not self.serve:
            raise ValueError("not a serving executable "
                             "(compile_supersteps(serve=True))")
        return self._fn(scrub_graph(graph), state, age, halted, overflow)

    def execute(self, graph: PartitionedGraph, state0: Any,
                num_real_queries: Optional[int] = None,
                checkpoint_every: Optional[int] = None,
                checkpoint_cb: Optional[Callable] = None,
                resume: Optional[dict] = None) -> RunResult:
        """One run. ``compile_time_s`` on the result is 0 — the caller
        that paid the compile stamps it (run_supersteps / Engine miss).

        num_real_queries: for a batched loop, how many leading query
        lanes are real (the rest are bucket padding) — every per-query
        view, total, and overflow report covers only those lanes.

        checkpoint_every/checkpoint_cb/resume: chunked-mode (unbatched)
        checkpointing — at the first dispatch boundary at or past every
        ``checkpoint_every`` supersteps, ``checkpoint_cb`` receives a
        host-side carry snapshot (step/state/accumulated traffic);
        ``resume`` restarts the loop from such a snapshot, bit-identical
        to the uninterrupted run (see ``repro.pregel.checkpoint``)."""
        # the executable was lowered against the scrubbed treedef, so any
        # same-signature graph replays (name/new_of_old identity dropped)
        graph = scrub_graph(graph)
        if self.serve:
            raise ValueError("serving executables are driven chunk by "
                             "chunk (serve_chunk / Engine.serve)")
        wants_ckpt = (checkpoint_every is not None or checkpoint_cb is not None
                      or resume is not None)
        if wants_ckpt and (self.mode != "chunked"
                           or self.num_queries is not None):
            raise ValueError(
                "checkpoint/resume needs the unbatched chunked substrate — "
                f"this executable is mode={self.mode!r}, num_queries="
                f"{self.num_queries}. Compile with mode='chunked' "
                "(Engine(mode='chunked')) to checkpoint at dispatch "
                "boundaries.")
        if self.num_queries is not None:
            res = _exec_batched(self._fn, graph, state0, self.mode,
                                self.max_steps, self.check_overflow,
                                self.num_queries,
                                num_real_queries or self.num_queries)
        elif self.mode == "host":
            res = _exec_host(self._fn, graph, state0, self.max_steps,
                             self.check_overflow)
        elif self.mode == "fused":
            res = _exec_fused(self._fn, graph, state0, self.check_overflow)
        else:
            res = _exec_chunked(self._fn, graph, state0, self.max_steps,
                                self.check_overflow,
                                checkpoint_every=checkpoint_every,
                                checkpoint_cb=checkpoint_cb, resume=resume)
        res.use_kernel = self.use_kernel
        res.route_impl = self.route_impl
        res.route_batch = self.route_batch if self.num_queries else ""
        return res


def compile_supersteps(
    graph: PartitionedGraph,
    step_fn: Callable,
    state0: Any,
    max_steps: int = 10_000,
    backend: str = "vmap",
    mesh: Optional[jax.sharding.Mesh] = None,
    axis: str = AXIS,
    check_overflow: bool = True,
    mode: Optional[str] = None,
    chunk_size: int = 64,
    channels: Optional[Any] = None,
    use_kernel: Optional[bool] = None,
    route_impl: Optional[str] = None,
    route_batch: Optional[str] = None,
    dense_threshold: Optional[float] = None,
    num_queries: Optional[int] = None,
    serve: bool = False,
    cap_scales: Optional[Dict[str, float]] = None,
) -> CompiledSupersteps:
    """Compile `step_fn(ctx, graph_shard, state_shard, step)` for a graph
    shape, without running it. See :func:`run_supersteps` for semantics.

    use_kernel / route_impl pin the data-plane configuration for the
    whole compile (None = resolve from env/backend defaults, see
    ``repro.kernels.ops`` / ``repro.core.routing``); explicit per-call
    channel arguments inside the step still win.

    num_queries=Q lowers the *batched* loop: the step is vmapped over a
    query axis inside the worker mapping, ``state0`` leaves must carry
    ``(W, Q, n_loc, ...)``, and halting/step counts/traffic are tracked
    per query (see the module docstring). The step function itself is
    unchanged — it still sees one query's ``(n_loc, ...)`` shard.

    route_batch selects how *routed* channels handle the query axis in a
    batched compile: ``"union"`` (default) shares ONE union-frontier
    bucket-route pass per superstep across all live lanes
    (``repro.core.routing.route_union``), ``"lane"`` routes each lane
    independently under the vmap (the pre-union behavior). Ignored when
    num_queries is None.

    serve=True (requires num_queries and mode="chunked") lowers the
    *serving* substrate instead: lanes are independent tenancies, so the
    step index each lane sees is its own age (steps since admission, a
    ``(Q,)`` carry leaf) rather than a shared loop counter, a lane's
    step budget is ``age < max_steps``, and the executable surfaces the
    chunk-boundary carry for the host-side lane swap
    (:meth:`CompiledSupersteps.serve_chunk`, ``repro.pregel.serve``).
    """
    # lower against the scrubbed graph: the compiled treedef must not
    # capture the host-only identity statics, or execute() could only
    # ever be called with this exact graph object
    graph = scrub_graph(graph)
    W, n_loc = graph.num_workers, graph.n_loc
    if mode is None:
        mode = "fused"
    if mode not in ("fused", "chunked", "host"):
        raise ValueError(f"unknown execution mode {mode!r}")
    if serve and (num_queries is None or mode != "chunked"):
        raise ValueError(
            "serve=True needs the chunked batched substrate "
            f"(num_queries=Q, mode='chunked'); got num_queries="
            f"{num_queries}, mode={mode!r}")

    traced_names: set = set()

    def make_shard_step(registry: Optional[ChannelRegistry]):
        def shard_step(g_shard, state_shard, step_idx, qinfo=None):
            # qinfo = (lane_index (), lane_live ()) under the query vmap —
            # the per-lane scalars routed channels use to share one
            # union-frontier route pass across lanes (route_batch="union")
            if qinfo is None:
                ctx = ChannelContext(axis, W, n_loc, registry=registry,
                                     cap_scales=cap_scales or {},
                                     route_cap=graph.route_cap)
            else:
                ctx = ChannelContext(
                    axis, W, n_loc, registry=registry,
                    cap_scales=cap_scales or {},
                    query_index=qinfo[0], query_live=qinfo[1],
                    num_queries=num_queries,
                    route_cap=graph.route_cap)
            with tracing.scope(tracing.COMPUTE):
                out = step_fn(ctx, g_shard, state_shard, step_idx)
            if len(out) == 3:
                new_state, halt, overflow = out
            else:
                new_state, halt = out
                overflow = jnp.asarray(False)
            with tracing.scope(tracing.VOTE):
                halt_all = aggregator.all_halted(ctx, halt)
                overflow_any = jax.lax.psum(
                    jnp.asarray(overflow, jnp.int32), axis) > 0
            traced_names.update(ctx.touched)  # host-side, at trace time
            nbytes, nmsgs = ctx.stats()
            novf = dict(ctx.stats_ovf)
            if backend == "shard_map":
                # vmap surfaces one stat scalar per worker ((W,) leaves,
                # summed host-side); shard_map's replicated out-spec would
                # surface only shard 0's local count, so reduce to the
                # global per-step total on device — same totals, either
                # backend
                psum = lambda v: jax.lax.psum(v, axis)
                with tracing.scope(tracing.VOTE):
                    nbytes = jax.tree_util.tree_map(psum, nbytes)
                    nmsgs = jax.tree_util.tree_map(psum, nmsgs)
                    novf = jax.tree_util.tree_map(
                        lambda v: jax.lax.psum(
                            jnp.asarray(v, jnp.int32), axis) > 0, novf)
            return new_state, halt_all, overflow_any, nbytes, nmsgs, novf

        return shard_step

    def map_shards(shard_step):
        if num_queries is not None:
            # the query axis rides INSIDE the worker mapping: each worker
            # advances all Q query instances of its shard; the axis-name
            # collectives inside the step batch transparently over Q. The
            # per-lane (index, live) scalars are batched alongside so the
            # union-frontier routed channels always see a Q-batched
            # operand (their custom_vmap rule fires on the query trace).
            # Serving compiles batch the step index too: each lane's
            # step function sees its own age, not a shared loop counter.
            step_ax = 0 if serve else None
            q_inner = jax.vmap(shard_step, in_axes=(None, 0, step_ax, 0))

            def shard_step_q(g_shard, state_shard, step_idx, live):
                qinfo = (jnp.arange(num_queries, dtype=jnp.int32),
                         jnp.asarray(live, bool))
                return q_inner(g_shard, state_shard, step_idx, qinfo)

            shard_step = shard_step_q
            worker_axes = (0, 0, None, None)
        else:
            worker_axes = (0, 0, None)
        if backend == "vmap":
            return jax.vmap(shard_step, in_axes=worker_axes, axis_name=axis)
        if backend == "shard_map":
            assert mesh is not None
            if mesh.shape[axis] != W:
                raise ValueError(
                    f"shard_map backend needs one worker per mesh device "
                    f"along {axis!r}: graph has W={W}, mesh axis size "
                    f"{mesh.shape[axis]}")
            P = jax.sharding.PartitionSpec

            def device_step(g_shard, state_shard, step_idx, *rest):
                # shard_map keeps the sharded axis as a leading size-1
                # dim; the step code (like vmap's) works on the bare
                # shard — peel it off and put it back on the state.
                # ``rest`` is the replicated (Q,) liveness vector on
                # batched compiles, empty otherwise.
                one = lambda x: x[0]
                new_state, halt, ovf, nb, nm, novf = shard_step(
                    jax.tree_util.tree_map(one, g_shard),
                    jax.tree_util.tree_map(one, state_shard),
                    step_idx,
                    *rest,
                )
                new_state = jax.tree_util.tree_map(
                    lambda x: x[None], new_state)
                return new_state, halt, ovf, nb, nm, novf

            extra = (P(),) if num_queries is not None else ()
            return jax.shard_map(
                device_step,
                mesh=mesh,
                in_specs=(P(axis), P(axis), P()) + extra,
                out_specs=(P(axis), P(), P(), P(), P(), P()),
                check_vma=False,
            )
        raise ValueError(backend)

    # --- channel registry. A `channels=` declaration IS the registry (no
    # dry trace at all — ChannelContext.add_traffic rejects undeclared
    # names when the step is traced for compilation below). Without a
    # declaration, the fused/chunked carries still need the fixed key set,
    # so discover it with a one-time jax.eval_shape dry trace (no compute).
    # Host mode consumes open per-step dicts and needs no registry. ------
    registry = None
    resolved_kernel = kops.resolve_use_kernel(use_kernel)
    resolved_route = routing.resolve_impl(route_impl)
    resolved_batch = routing.resolve_batch(route_batch)
    resolved_thresh = compose.resolve_dense_threshold(dense_threshold)
    # the data-plane choice is baked in at trace time: every channel call
    # that did not pass an explicit argument resolves through these scopes
    with kops.use_kernel_scope(resolved_kernel), \
            routing.impl_scope(resolved_route), \
            routing.batch_scope(resolved_batch), \
            compose.dense_threshold_scope(resolved_thresh):
        if channels is not None:
            names = compose.channel_names_of(channels)
            # the mapped step's per-step stat leaf is (W,) under vmap (one
            # scalar per logical worker) and () under shard_map (replicated);
            # a query axis appends Q as the trailing dimension
            stat_shape = (W,) if backend == "vmap" else ()
            if num_queries is not None:
                stat_shape = stat_shape + (num_queries,)
            registry = ChannelRegistry.declare(sorted(names), shape=stat_shape)
        elif mode in ("fused", "chunked"):
            probe = map_shards(make_shard_step(None))
            if serve:
                step_probe = jnp.zeros((num_queries,), jnp.int32)
            else:
                step_probe = jnp.asarray(0, jnp.int32)
            probe_args = (graph, state0, step_probe)
            if num_queries is not None:
                probe_args += (jnp.ones((num_queries,), bool),)
            out_struct = jax.eval_shape(probe, *probe_args)
            _, _, _, bytes_struct, _, _ = out_struct
            registry = ChannelRegistry.from_stats_structure(bytes_struct)

        mapped = map_shards(make_shard_step(registry))
        i0 = jnp.asarray(0, jnp.int32)

        def jit(fn):
            # every instruction of the loop is superstep work
            return jax.jit(tracing.scope(tracing.SUPERSTEP)(fn))

        tc = time.perf_counter()
        with tracing.span(tracing.COMPILE):
            if num_queries is not None:
                h0 = jnp.zeros((num_queries,), bool)
                if serve:
                    a0 = jnp.zeros((num_queries,), jnp.int32)
                    fn = (jit(_make_serve_chunk(
                            mapped, registry, max_steps, check_overflow,
                            chunk_size, num_queries))
                          .lower(graph, state0, a0, h0, h0).compile())
                elif mode == "host":
                    fn = (jit(_make_batched_step(mapped, num_queries))
                          .lower(graph, state0, i0, h0).compile())
                elif mode == "fused":
                    fn = (jit(_make_batched_fused_loop(
                            mapped, registry, max_steps, check_overflow,
                            num_queries))
                          .lower(graph, state0, h0).compile())
                else:
                    fn = (jit(_make_batched_chunk(
                            mapped, registry, max_steps, check_overflow,
                            chunk_size, num_queries))
                          .lower(graph, state0, i0, h0, h0).compile())
            elif mode == "host":
                fn = jit(mapped).lower(graph, state0, i0).compile()
            elif mode == "fused":
                fn = (
                    jit(_make_fused_loop(mapped, registry, max_steps,
                                             check_overflow))
                    .lower(graph, state0)
                    .compile()
                )
            else:
                f = jnp.zeros((), bool)
                fn = (
                    jit(_make_chunk(mapped, registry, max_steps,
                                        check_overflow, chunk_size))
                    .lower(graph, state0, i0, f, f)
                    .compile()
                )
        compile_s = time.perf_counter() - tc
        tracing.register(fn)

    # both validation directions without a dry trace: an undeclared
    # traced channel raised from add_traffic during the AOT trace above;
    # a declared-but-never-traced channel is caught here (it would
    # otherwise report phantom zero rows forever)
    if channels is not None:
        phantom = set(registry.names) - traced_names
        if phantom:
            raise ValueError(
                f"declared channels {tuple(sorted(phantom))} were never "
                f"traced by the step function (traced: "
                f"{tuple(sorted(traced_names))}) — stale or misspelled "
                "declaration"
            )

    return CompiledSupersteps(
        mode=mode,
        max_steps=max_steps,
        check_overflow=check_overflow,
        chunk_size=chunk_size,
        registry=registry,
        compile_time_s=compile_s,
        _fn=fn,
        use_kernel=resolved_kernel,
        route_impl=resolved_route,
        route_batch=resolved_batch,
        dense_threshold=resolved_thresh,
        num_queries=num_queries,
        serve=serve,
    )


def run_supersteps(
    graph: PartitionedGraph,
    step_fn: Callable,
    state0: Any,
    max_steps: int = 10_000,
    backend: str = "vmap",
    mesh: Optional[jax.sharding.Mesh] = None,
    axis: str = AXIS,
    check_overflow: bool = True,
    mode: Optional[str] = None,
    chunk_size: int = 64,
    channels: Optional[Any] = None,
    use_kernel: Optional[bool] = None,
    route_impl: Optional[str] = None,
    route_batch: Optional[str] = None,
) -> RunResult:
    """Run `step_fn(ctx, graph_shard, state_shard, step)` to halt.

    state0: pytree with per-vertex leaves of shape (W, n_loc, ...).
    step_fn returns (new_state, halt_local_bool) and may also return a
    third element `overflow` (bool) which the runtime surfaces as an error.

    mode: "fused" (default), "chunked", or "host" — see module docstring.
    channels: optional explicit channel declaration — a sequence of
      stat-key names, a composed channel (any object with
      ``channel_names()``, e.g. ``repro.core.compose.Stacked``), or a
      mixed sequence of both. Declared programs skip the eval_shape dry
      trace; the declaration is validated lazily by
      ``ChannelContext.add_traffic`` (an undeclared channel raises while
      the step is traced for compilation).

    Compiles per call; hold a ``repro.pregel.engine.Engine`` to reuse
    compiles across runs and same-shape graphs.
    """
    exe = compile_supersteps(
        graph, step_fn, state0, max_steps=max_steps, backend=backend,
        mesh=mesh, axis=axis, check_overflow=check_overflow, mode=mode,
        chunk_size=chunk_size, channels=channels, use_kernel=use_kernel,
        route_impl=route_impl, route_batch=route_batch,
    )
    res = exe.execute(graph, state0)
    res.compile_time_s = exe.compile_time_s
    return res


# ---------------------------------------------------------------------------
# host mode: one dispatch + blocking readback per superstep (baseline)
# ---------------------------------------------------------------------------


def _exec_host(stepper, graph, state0, max_steps, check_overflow) -> RunResult:
    bytes_acc: Dict[str, int] = {}
    msgs_acc: Dict[str, int] = {}
    ovf_acc: Dict[str, bool] = {}
    state = state0
    halted = False
    t0 = time.perf_counter()
    step_times = []
    overhead = 0.0
    overflowed = False
    wrapped_keys: set = set()
    step = -1  # so max_steps=0 reports zero executed supersteps
    for step in range(max_steps):
        ts = time.perf_counter()
        with tracing.span("job/dispatch"):
            state, halt_all, overflow, nbytes, nmsgs, novf = stepper(
                graph, state, jnp.asarray(step, jnp.int32)
            )
        t_enq = time.perf_counter()
        with tracing.span("job/wait"):
            jax.block_until_ready(state)
        t_dev = time.perf_counter()
        step_times.append(t_dev - ts)
        with tracing.span("job/readback"):
            for k, v in nbytes.items():
                d = _host_int(v)
                if d < 0:
                    wrapped_keys.add(k)
                bytes_acc[k] = bytes_acc.get(k, 0) + d
            for k, v in nmsgs.items():
                d = _host_int(v)
                if d < 0:
                    wrapped_keys.add(k)
                msgs_acc[k] = msgs_acc.get(k, 0) + d
            for k, v in novf.items():
                ovf_acc[k] = (ovf_acc.get(k, False)
                              or bool(np.asarray(v).any()))
            halt_now = bool(np.asarray(halt_all).reshape(-1)[0])
        # dispatch enqueue plus readback/bookkeeping time: the host cost
        # of driving one step (the stepper is AOT-compiled, so step 0 is
        # an ordinary dispatch)
        overhead += t_enq - ts
        overhead += time.perf_counter() - t_dev
        if check_overflow and bool(np.asarray(overflow).reshape(-1)[0]):
            overflowed = True
            break
        if wrapped_keys:
            break
        if halt_now:
            halted = True
            break
    wall = time.perf_counter() - t0
    res = RunResult(
        state=state,
        steps=step + 1,
        halted=halted,
        bytes_by_channel=bytes_acc,
        msgs_by_channel=msgs_acc,
        wall_time_s=wall,
        step_times_s=step_times,
        mode="host",
        dispatches=step + 1,
        host_overhead_s=overhead,
        converged=halted,
        overflow_by_channel=ovf_acc,
    )
    if overflowed:
        bad = sorted(k for k, v in ovf_acc.items() if v)
        raise errors.ChannelOverflowError(
            errors.overflow_message(step, bad),
            superstep=step, channels=bad, result=res)
    if wrapped_keys:
        bad = sorted(wrapped_keys)
        raise errors.TrafficWrapError(
            f"int32 traffic counter wrapped in channel(s) {', '.join(bad)} "
            f"at superstep {step} — per-step traffic exceeds int32 range",
            superstep=step, channels=bad, result=res)
    return res


# ---------------------------------------------------------------------------
# fused mode: the entire superstep loop is one lax.while_loop dispatch
# ---------------------------------------------------------------------------


def _make_fused_loop(mapped, registry, max_steps, check_overflow):
    zeros = registry.zeros()
    flags = registry.flags()

    def loop(graph, state):
        @tracing.scope(tracing.VOTE)
        def cond(carry):
            _, i, halted, overflow, _, _, _, _ = carry
            go = (~halted) & (i < max_steps)
            if check_overflow:
                go = go & (~overflow)
            return go

        def body(carry):
            state, i, _, overflow, nb, nm, ovf_by, wrapped = carry
            new_state, halt, ovf, db, dm, dovf = mapped(graph, state, i)
            with tracing.scope(tracing.VOTE):
                nb2 = jax.tree_util.tree_map(jnp.add, nb, db)
                nm2 = jax.tree_util.tree_map(jnp.add, nm, dm)
                ovf_by2 = jax.tree_util.tree_map(jnp.logical_or, ovf_by,
                                                 dovf)
                # per-step deltas are non-negative, so a decreasing
                # accumulator means the int32 counter wrapped — latch it
                # for the host
                for old, new in ((nb, nb2), (nm, nm2)):
                    for o, n in zip(jax.tree_util.tree_leaves(old),
                                    jax.tree_util.tree_leaves(new)):
                        wrapped = wrapped | jnp.any(n < o)
                return (new_state, i + 1, _scalar(halt),
                        overflow | _scalar(ovf), nb2, nm2, ovf_by2, wrapped)

        init = (state, jnp.asarray(0, jnp.int32), jnp.zeros((), bool),
                jnp.zeros((), bool), zeros, zeros, flags,
                jnp.zeros((), bool))
        return jax.lax.while_loop(cond, body, init)

    return loop


def _exec_fused(compiled, graph, state0, check_overflow) -> RunResult:
    t0 = time.perf_counter()
    with tracing.span("job/dispatch"):
        out = compiled(graph, state0)
    state, steps, halted, overflow, nb, nm, novf, wrapped = out
    t_enq = time.perf_counter()
    with tracing.span("job/wait"):
        jax.block_until_ready(state)
    t_dev = time.perf_counter()
    wall = t_dev - t0

    with tracing.span("job/readback"):
        steps = int(np.asarray(steps))
        halted_b = bool(np.asarray(halted))
        bytes_by = {k: _host_int(v) for k, v in nb.items()}
        msgs_by = {k: _host_int(v) for k, v in nm.items()}
        ovf_by = {k: bool(np.asarray(v).any()) for k, v in novf.items()}
        overflowed = bool(np.asarray(overflow))
        wrapped = bool(np.asarray(wrapped))
    overhead = (t_enq - t0) + (time.perf_counter() - t_dev)
    res = RunResult(
        state=state,
        steps=steps,
        halted=halted_b,
        bytes_by_channel=bytes_by,
        msgs_by_channel=msgs_by,
        wall_time_s=wall,
        step_times_s=[wall],
        mode="fused",
        dispatches=1,
        host_overhead_s=overhead,
        converged=halted_b,
        overflow_by_channel=ovf_by,
    )
    if check_overflow and overflowed:
        bad = sorted(k for k, v in ovf_by.items() if v)
        raise errors.ChannelOverflowError(
            errors.overflow_message(steps - 1, bad),
            superstep=steps - 1, channels=bad, result=res)
    if wrapped:
        # the fused latch is global (accumulator decreased) — no
        # per-channel attribution on device
        raise errors.TrafficWrapError(
            "per-channel traffic counters overflowed int32 inside the fused "
            "loop; bytes/msgs totals are unreliable — use mode='chunked' "
            "(exact host-side int64 accumulation) for runs this heavy",
            superstep=steps - 1, result=res)
    return res


# ---------------------------------------------------------------------------
# chunked mode: lax.scan over K supersteps per dispatch; the host streams
# per-step stats (exact int64 accumulation) at every chunk boundary
# ---------------------------------------------------------------------------


def _make_chunk(mapped, registry, max_steps, check_overflow, chunk_size):
    K = max(1, min(chunk_size, max_steps))
    zeros = registry.zeros()
    flags = registry.flags()

    def chunk(graph, state, i0, halted0, overflow0):
        def body(carry, _):
            state, i, halted, overflow = carry
            with tracing.scope(tracing.VOTE):
                stop = halted | (i >= max_steps)
                if check_overflow:
                    stop = stop | overflow

            def do(operand):
                state, i = operand
                new_state, halt, ovf, db, dm, dovf = mapped(graph, state, i)
                with tracing.scope(tracing.VOTE):
                    return ((new_state, i + 1, _scalar(halt),
                             overflow | _scalar(ovf)), (db, dm, dovf))

            def skip(operand):
                state, i = operand
                # skipped steps contribute zero traffic
                return ((state, i, halted, overflow),
                        (zeros, zeros, flags))

            return jax.lax.cond(stop, skip, do, (state, i))

        (state, i, halted, overflow), (db, dm, dovf) = jax.lax.scan(
            body, (state, i0, halted0, overflow0), None, length=K
        )
        return state, i, halted, overflow, db, dm, dovf

    return chunk


# ---------------------------------------------------------------------------
# batched query plane: one loop advances Q query instances per superstep,
# with per-query halt voting, frozen state for halted queries, and
# per-query step/traffic attribution (engine.Engine.run_batch rides this)
# ---------------------------------------------------------------------------


def _qrow(x, q: int):
    """(Q,) view of a per-query flag that may be worker-replicated
    ((W, Q) under vmap, (Q,) under shard_map)."""
    return jnp.asarray(x).reshape((-1, q))[0]


def _qmask(live, leaf):
    """Broadcast a (Q,) liveness mask against a (W, Q, ...) state leaf."""
    return live.reshape((1,) + live.shape + (1,) * (leaf.ndim - 2))


def _host_q(v, q: int) -> np.ndarray:
    """Stat leaf with trailing query axis -> (Q,) int64 per-query totals
    (sums any leading worker/chunk axes)."""
    return np.asarray(v).astype(np.int64).reshape((-1, q)).sum(axis=0)


def _make_batched_step(mapped, q: int):
    """One batched superstep with the per-query bookkeeping folded in:
    halted queries keep their state bit-for-bit (their lanes still
    compute, the result is discarded) and contribute zero traffic and no
    overflow. Shared by all three batched modes — host compiles it
    directly, fused/chunked call it from their loop bodies."""

    def bstep(graph, state, i, halted):
        with tracing.scope(tracing.VOTE):
            live = ~halted
        new_state, halt, ovf, db, dm, dovf = mapped(graph, state, i, live)
        with tracing.scope(tracing.VOTE):
            new_state = jax.tree_util.tree_map(
                lambda n, o: jnp.where(_qmask(live, n), n, o), new_state,
                state)
            # stat leaves have the query axis last ((W, Q) / (Q,)) — the
            # (Q,) mask broadcasts; the halting step itself still charges
            # (live is the PRE-step vote, matching Q independent runs)
            db = jax.tree_util.tree_map(lambda d: jnp.where(live, d, 0), db)
            dm = jax.tree_util.tree_map(lambda d: jnp.where(live, d, 0), dm)
            dovf = jax.tree_util.tree_map(
                lambda d: jnp.where(live, d, False), dovf)
            return (new_state, halted | _qrow(halt, q),
                    _qrow(ovf, q) & live, db, dm, dovf)

    return bstep


def _make_batched_fused_loop(mapped, registry, max_steps, check_overflow, q):
    zeros = registry.zeros()
    flags = registry.flags()
    bstep = _make_batched_step(mapped, q)

    # halted0 is an argument (not a constant) so bucket-padding lanes can
    # start halted: a pad lane then never steps, never reaches the union
    # route pass (query_live=False end to end), and is never charged
    def loop(graph, state, halted0):
        @tracing.scope(tracing.VOTE)
        def cond(carry):
            _, i, halted, overflow, _, _, _, _, _ = carry
            go = jnp.any(~halted) & (i < max_steps)
            if check_overflow:
                go = go & ~jnp.any(overflow)
            return go

        def body(carry):
            state, i, halted, overflow, steps_q, nb, nm, ovf_by, wrapped = (
                carry)
            new_state, halted2, ovf_q, db, dm, dovf = bstep(
                graph, state, i, halted)
            with tracing.scope(tracing.VOTE):
                nb2 = jax.tree_util.tree_map(jnp.add, nb, db)
                nm2 = jax.tree_util.tree_map(jnp.add, nm, dm)
                ovf_by2 = jax.tree_util.tree_map(jnp.logical_or, ovf_by,
                                                 dovf)
                for old, new in ((nb, nb2), (nm, nm2)):
                    for o, n in zip(jax.tree_util.tree_leaves(old),
                                    jax.tree_util.tree_leaves(new)):
                        wrapped = wrapped | jnp.any(n < o)
                steps_q = steps_q + (~halted).astype(jnp.int32)
                return (new_state, i + 1, halted2, overflow | ovf_q,
                        steps_q, nb2, nm2, ovf_by2, wrapped)

        qz = jnp.zeros((q,), bool)
        init = (state, jnp.asarray(0, jnp.int32), jnp.asarray(halted0, bool),
                qz, jnp.zeros((q,), jnp.int32), zeros, zeros, flags,
                jnp.zeros((), bool))
        return jax.lax.while_loop(cond, body, init)

    return loop


def _make_batched_chunk(mapped, registry, max_steps, check_overflow,
                        chunk_size, q):
    K = max(1, min(chunk_size, max_steps))
    zeros = registry.zeros()
    flags = registry.flags()
    bstep = _make_batched_step(mapped, q)

    def chunk(graph, state, i0, halted0, overflow0):
        def body(carry, _):
            state, i, halted, overflow, steps_q = carry
            with tracing.scope(tracing.VOTE):
                stop = jnp.all(halted) | (i >= max_steps)
                if check_overflow:
                    stop = stop | jnp.any(overflow)

            def do(operand):
                state, i, halted, overflow, steps_q = operand
                new_state, halted2, ovf_q, db, dm, dovf = bstep(
                    graph, state, i, halted)
                with tracing.scope(tracing.VOTE):
                    steps_q = steps_q + (~halted).astype(jnp.int32)
                    return ((new_state, i + 1, halted2, overflow | ovf_q,
                             steps_q), (db, dm, dovf))

            def skip(operand):
                return (operand, (zeros, zeros, flags))

            return jax.lax.cond(stop, skip, do,
                                (state, i, halted, overflow, steps_q))

        (state, i, halted, overflow, steps_q), (db, dm, dovf) = jax.lax.scan(
            body, (state, i0, halted0, overflow0,
                   jnp.zeros((q,), jnp.int32)),
            None, length=K)
        return state, i, halted, overflow, steps_q, db, dm, dovf

    return chunk


def _make_serve_chunk(mapped, registry, max_steps, check_overflow,
                      chunk_size, q):
    """The serving substrate (``Engine.serve``): a scan of up to
    ``chunk_size`` supersteps whose carry is per-lane ``(age, halted,
    overflow)`` instead of a shared loop counter.

    Each lane is an independent tenancy: its step function sees its own
    ``age`` as the step index (so a query admitted at global superstep 40
    is bit-identical to a solo run starting at 0), its budget is ``age <
    max_steps``, and a lane that is halted, budget-exhausted, or
    unoccupied (the host marks it halted) is *dead* — state frozen bit
    for bit, traffic masked to zero, excluded from the union route pass
    via ``query_live``. The scan skips remaining iterations once every
    lane is dead, so a chunk never does work past its last live step."""
    K = max(1, chunk_size)
    zeros = registry.zeros()
    flags = registry.flags()

    def chunk(graph, state, age0, halted0, overflow0):
        def body(carry, _):
            state, age, halted, overflow = carry
            with tracing.scope(tracing.VOTE):
                dead = halted | (age >= max_steps)
                stop = jnp.all(dead)
                if check_overflow:
                    stop = stop | jnp.any(overflow)

            def do(operand):
                state, age, halted, overflow = operand
                with tracing.scope(tracing.VOTE):
                    live = ~(halted | (age >= max_steps))
                new_state, halt, ovf, db, dm, dovf = mapped(
                    graph, state, age, live)
                with tracing.scope(tracing.VOTE):
                    new_state = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(_qmask(live, n), n, o),
                        new_state, state)
                    db = jax.tree_util.tree_map(
                        lambda d: jnp.where(live, d, 0), db)
                    dm = jax.tree_util.tree_map(
                        lambda d: jnp.where(live, d, 0), dm)
                    dovf = jax.tree_util.tree_map(
                        lambda d: jnp.where(live, d, False), dovf)
                    # only a live lane's own vote may halt it: a dead
                    # lane's (discarded) computation must not flip its
                    # flags
                    halted2 = halted | (_qrow(halt, q) & live)
                    overflow2 = overflow | (_qrow(ovf, q) & live)
                    return ((new_state, age + live.astype(jnp.int32),
                             halted2, overflow2),
                            (db, dm, dovf, live.astype(jnp.int32)))

            def skip(operand):
                return (operand,
                        (zeros, zeros, flags, jnp.zeros((q,), jnp.int32)))

            return jax.lax.cond(stop, skip, do,
                                (state, age, halted, overflow))

        (state, age, halted, overflow), (db, dm, dovf, lives) = jax.lax.scan(
            body,
            (state, jnp.asarray(age0, jnp.int32),
             jnp.asarray(halted0, bool), jnp.asarray(overflow0, bool)),
            None, length=K)
        return state, age, halted, overflow, lives.sum(axis=0), db, dm, dovf

    return chunk


def _host_q_flag(v, q: int) -> np.ndarray:
    """Overflow flag leaf with trailing query axis -> (Q,) bool (ORs any
    leading worker/chunk axes)."""
    return np.asarray(v).astype(bool).reshape((-1, q)).any(axis=0)


def _batched_result(state, steps, halted_q, overflow_q, q_bytes, q_msgs,
                    steps_q, q_real, mode, dispatches, wall, step_times,
                    overhead, check_overflow, ovf_by=None,
                    wrapped=False) -> RunResult:
    # report only the real leading lanes — bucket-padding lanes (which
    # start halted) never surface in views, totals, or errors; their
    # aggregates ride along as the dead-pad audit trail (all zero)
    num_pad = len(steps_q) - q_real
    pad_steps = int(steps_q[q_real:].sum())
    pad_bytes = int(sum(v[q_real:].sum() for v in q_bytes.values()))
    pad_msgs = int(sum(v[q_real:].sum() for v in q_msgs.values()))
    halted_q = halted_q[:q_real]
    overflow_q = overflow_q[:q_real]
    steps_q = steps_q[:q_real]
    q_bytes = {k: v[:q_real] for k, v in q_bytes.items()}
    q_msgs = {k: v[:q_real] for k, v in q_msgs.items()}
    ovf_by = {k: v[:q_real] for k, v in (ovf_by or {}).items()}
    res = RunResult(
        state=state,
        steps=steps,
        halted=bool(halted_q.all()),
        bytes_by_channel={k: int(v.sum()) for k, v in q_bytes.items()},
        msgs_by_channel={k: int(v.sum()) for k, v in q_msgs.items()},
        wall_time_s=wall,
        step_times_s=step_times,
        mode=mode,
        dispatches=dispatches,
        host_overhead_s=overhead,
        num_queries=q_real,
        query_steps=steps_q,
        query_halted=halted_q,
        query_bytes_by_channel=q_bytes,
        query_msgs_by_channel=q_msgs,
        num_pad_lanes=num_pad,
        pad_steps=pad_steps,
        pad_bytes=pad_bytes,
        pad_msgs=pad_msgs,
        converged=bool(halted_q.all()),
        overflow_by_channel=ovf_by,
    )
    if check_overflow and overflow_q.any():
        qs = np.flatnonzero(overflow_q).tolist()
        bad = sorted(k for k, v in ovf_by.items() if np.asarray(v).any())
        raise errors.ChannelOverflowError(
            errors.overflow_message(steps - 1, bad, qids=qs),
            superstep=steps - 1, channels=bad, result=res, qids=qs)
    if wrapped:
        raise errors.TrafficWrapError(
            "per-channel traffic counters overflowed int32 inside the "
            "batched loop; bytes/msgs totals are unreliable — use "
            "mode='chunked' (exact host-side int64 accumulation) for "
            "runs this heavy",
            superstep=steps - 1, result=res)
    return res


def _exec_batched(compiled, graph, state0, mode, max_steps, check_overflow,
                  q, q_real) -> RunResult:
    # bucket-padding lanes start halted: dead end to end (no steps, no
    # wire slots, no traffic) instead of shadow-running query 0
    pad_halted = jnp.arange(q) >= q_real
    if mode == "fused":
        t0 = time.perf_counter()
        with tracing.span("job/dispatch"):
            out = compiled(graph, state0, pad_halted)
        t_enq = time.perf_counter()
        state, steps, halted, overflow, steps_q, nb, nm, novf, wrapped = out
        with tracing.span("job/wait"):
            jax.block_until_ready(state)
        t_dev = time.perf_counter()
        wall = t_dev - t0
        overhead = (t_enq - t0) + (time.perf_counter() - t_dev)
        with tracing.span("job/readback"):
            return _batched_result(
                state, int(np.asarray(steps)), np.asarray(halted),
                np.asarray(overflow),
                {k: _host_q(v, q) for k, v in nb.items()},
                {k: _host_q(v, q) for k, v in nm.items()},
                np.asarray(steps_q).astype(np.int64), q_real, mode, 1, wall,
                [wall], overhead, check_overflow,
                ovf_by={k: _host_q_flag(v, q) for k, v in novf.items()},
                wrapped=bool(np.asarray(wrapped)))

    q_bytes: Dict[str, np.ndarray] = {}
    q_msgs: Dict[str, np.ndarray] = {}
    q_ovf: Dict[str, np.ndarray] = {}
    wrapped = False

    def acc(into, delta):
        nonlocal wrapped
        for k, v in delta.items():
            row = _host_q(v, q)
            if (row < 0).any():
                wrapped = True
            into[k] = into.get(k, 0) + row

    def acc_ovf(delta):
        for k, v in delta.items():
            row = _host_q_flag(v, q)
            q_ovf[k] = q_ovf.get(k, False) | row

    state = state0
    halted = pad_halted
    steps_q = np.zeros((q,), np.int64)
    overflow_acc = np.zeros((q,), bool)
    step_times = []
    dispatches = 0
    overhead = 0.0
    steps = 0
    t0 = time.perf_counter()

    if mode == "host":
        for step in range(max_steps):
            live = ~np.asarray(halted)
            if not live.any():
                break
            ts = time.perf_counter()
            with tracing.span("job/dispatch"):
                state, halted, ovf_q, db, dm, dovf = compiled(
                    graph, state, jnp.asarray(step, jnp.int32), halted)
            t_enq = time.perf_counter()
            with tracing.span("job/wait"):
                jax.block_until_ready(state)
            t_dev = time.perf_counter()
            step_times.append(t_dev - ts)
            dispatches += 1
            steps = step + 1
            steps_q += live
            with tracing.span("job/readback"):
                acc(q_bytes, db)
                acc(q_msgs, dm)
                acc_ovf(dovf)
                overflow_acc |= np.asarray(ovf_q)
            overhead += (t_enq - ts) + (time.perf_counter() - t_dev)
            if check_overflow and overflow_acc[:q_real].any():
                break
            if wrapped:
                break
    else:  # chunked
        i = jnp.asarray(0, jnp.int32)
        overflow = jnp.zeros((q,), bool)
        while True:
            ts = time.perf_counter()
            with tracing.span("job/dispatch"):
                state, i, halted, overflow, d_steps, db, dm, dovf = compiled(
                    graph, state, i, halted, overflow)
            t_enq = time.perf_counter()
            with tracing.span("job/wait"):
                jax.block_until_ready(state)
            t_dev = time.perf_counter()
            step_times.append(t_dev - ts)
            dispatches += 1
            with tracing.span("job/readback"):
                steps = int(np.asarray(i))
                steps_q += np.asarray(d_steps).astype(np.int64)
                acc(q_bytes, db)
                acc(q_msgs, dm)
                acc_ovf(dovf)
                overflow_acc |= np.asarray(overflow)
            overhead += (t_enq - ts) + (time.perf_counter() - t_dev)
            if check_overflow and overflow_acc[:q_real].any():
                break
            if wrapped:
                break
            if bool(np.asarray(halted).all()) or steps >= max_steps:
                break

    wall = time.perf_counter() - t0
    return _batched_result(
        state, steps, np.asarray(halted), overflow_acc, q_bytes, q_msgs,
        steps_q, q_real, mode, dispatches, wall, step_times, overhead,
        check_overflow, ovf_by=q_ovf, wrapped=wrapped)


def _exec_chunked(compiled, graph, state0, max_steps, check_overflow,
                  checkpoint_every: Optional[int] = None,
                  checkpoint_cb: Optional[Callable] = None,
                  resume: Optional[dict] = None) -> RunResult:
    f = jnp.zeros((), bool)
    bytes_acc: Dict[str, int] = {}
    msgs_acc: Dict[str, int] = {}
    ovf_acc: Dict[str, bool] = {}
    state = state0
    i = jnp.asarray(0, jnp.int32)
    halted, overflow = f, f
    resumed_from = 0
    if resume is not None:
        # restart from a dispatch-boundary snapshot: the scan continues
        # with the exact carry the uninterrupted run had at this boundary,
        # so states/steps/traffic replay bit for bit
        state = jax.tree_util.tree_map(jnp.asarray, resume["state"])
        i = jnp.asarray(int(resume["step"]), jnp.int32)
        bytes_acc = dict(resume["bytes_by_channel"])
        msgs_acc = dict(resume["msgs_by_channel"])
        ovf_acc = dict(resume.get("overflow_by_channel", {}))
        resumed_from = int(resume["step"])
    next_due = (resumed_from + checkpoint_every
                if checkpoint_every else None)
    chunk_times = []
    dispatches = 0
    overhead = 0.0
    wrapped_keys: set = set()
    t0 = time.perf_counter()
    while True:
        ts = time.perf_counter()
        with tracing.span("job/dispatch"):
            state, i, halted, overflow, db, dm, dovf = compiled(
                graph, state, i, halted, overflow
            )
        t_enq = time.perf_counter()
        with tracing.span("job/wait"):
            jax.block_until_ready(state)
        t_dev = time.perf_counter()
        chunk_times.append(t_dev - ts)
        dispatches += 1
        # stream the chunk's per-step stats out (skipped steps are zero);
        # a negative per-step delta is an in-step int32 wrap
        with tracing.span("job/readback"):
            for k, v in db.items():
                if (np.asarray(v) < 0).any():
                    wrapped_keys.add(k)
                bytes_acc[k] = bytes_acc.get(k, 0) + _host_int(v)
            for k, v in dm.items():
                if (np.asarray(v) < 0).any():
                    wrapped_keys.add(k)
                msgs_acc[k] = msgs_acc.get(k, 0) + _host_int(v)
            for k, v in dovf.items():
                ovf_acc[k] = (ovf_acc.get(k, False)
                              or bool(np.asarray(v).any()))
            steps = int(np.asarray(i))
            halt_now = bool(np.asarray(halted))
            overflowed = check_overflow and bool(np.asarray(overflow))
        overhead += (t_enq - ts) + (time.perf_counter() - t_dev)
        if overflowed or wrapped_keys:
            break
        if halt_now or steps >= max_steps:
            break
        if (checkpoint_cb is not None and next_due is not None
                and steps >= next_due):
            checkpoint_cb({
                "step": steps,
                "state": jax.tree_util.tree_map(np.asarray, state),
                "bytes_by_channel": dict(bytes_acc),
                "msgs_by_channel": dict(msgs_acc),
                "overflow_by_channel": dict(ovf_acc),
                "dispatches": dispatches,
            })
            next_due = steps + checkpoint_every
    wall = time.perf_counter() - t0
    halted_b = bool(np.asarray(halted))
    res = RunResult(
        state=state,
        steps=steps,
        halted=halted_b,
        bytes_by_channel=bytes_acc,
        msgs_by_channel=msgs_acc,
        wall_time_s=wall,
        step_times_s=chunk_times,
        mode="chunked",
        dispatches=dispatches,
        compile_time_s=0.0,
        host_overhead_s=overhead,
        converged=halted_b,
        overflow_by_channel=ovf_acc,
        resumed_from=resumed_from,
    )
    if overflowed:
        bad = sorted(k for k, v in ovf_acc.items() if v)
        raise errors.ChannelOverflowError(
            errors.overflow_message(steps - 1, bad),
            superstep=steps - 1, channels=bad, result=res)
    if wrapped_keys:
        bad = sorted(wrapped_keys)
        raise errors.TrafficWrapError(
            f"int32 traffic counter wrapped in channel(s) {', '.join(bad)} "
            f"by superstep {steps - 1} — per-step traffic exceeds int32 "
            "range", superstep=steps - 1, channels=bad, result=res)
    return res
