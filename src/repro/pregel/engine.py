"""Engine — a compile-once execution session for VertexPrograms.

Every legacy ``run(...)`` call re-traced and re-compiled its superstep
loop, even when the same algorithm ran again on the same (or a
same-shape) graph — per-call compile latency that dominates small runs
and multiplies across benchmarks sweeps. An :class:`Engine` is the
session object that amortizes it: it compiles a
(:class:`~repro.pregel.program.VertexProgram`, graph-shape, mode) key at
most once and replays the cached executable for every subsequent run.

    eng = Engine(mode="fused")
    res1 = eng.run(prog, pg_a)      # compiles
    res2 = eng.run(prog, pg_a)      # cache hit — no trace, no compile
    res3 = eng.run(prog, pg_b)      # cache hit too, if pg_b has pg_a's
                                    # shape signature (identical caps)

Cache telemetry lives on the engine (``compiles`` / ``cache_hits``) and
is stamped onto every ``RunResult`` (``cache_hit``, ``engine_compiles``,
``engine_cache_hits``) so benchmarks can report exactly what a session
paid. Correctness does not depend on the cache: a shape signature covers
*every* static that enters the compiled loop (see
:func:`repro.pregel.runtime.graph_signature`), so a hit is bit-identical
to a fresh compile.

:meth:`Engine.run_batch` is the batched query plane: one compiled loop
advances Q query instances (e.g. Q SSSP sources) of a query-parametric
program (``VertexProgram.query_init``) per superstep, with per-query
halt voting and per-query step/traffic attribution. The compile-cache
key uses the *power-of-two-bucketed* batch cap, not Q itself — the
batch is padded to the bucket by repeating the first query, so Q=20 and
Q=27 share one executable (the same trick the graph plans play with
their slot caps).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import compose, routing
from repro.graph.pgraph import PartitionedGraph
from repro.kernels import ops as kops
from repro.plan import features, planner as planning
from repro.pregel import checkpoint as ckpt_io
from repro.pregel import errors
from repro.pregel import runtime
from repro.pregel import serve as serving
from repro.pregel import tracing
from repro.pregel.program import VertexProgram


def bucket_queries(q: int) -> int:
    """Pow2 batch cap: the compiled query-axis width for a Q-query batch."""
    if q < 1:
        raise ValueError(f"need at least one query, got {q}")
    return 1 << (q - 1).bit_length()


class Engine:
    """Compile-once session for running VertexPrograms.

    backend/mesh/mode/chunk_size are fixed per engine (they select the
    compiled artifact); hold one engine per execution configuration and
    as many programs/graphs as you like flow through it.
    """

    def __init__(self, backend: str = "vmap",
                 mesh: Optional[jax.sharding.Mesh] = None,
                 mode: Optional[str] = None,
                 chunk_size: Optional[int] = None,
                 use_kernel: Optional[bool] = None,
                 route_impl: Optional[str] = None,
                 route_batch: Optional[str] = None,
                 dense_threshold: Optional[float] = None,
                 plan: Any = "manual",
                 on_overflow: str = "raise",
                 on_nonconverged: Optional[str] = None,
                 cap_scales: Optional[Dict[str, float]] = None,
                 max_retries: int = 8):
        if mode is not None and mode not in ("fused", "chunked", "host"):
            raise ValueError(f"unknown execution mode {mode!r}")
        if not (plan in ("manual", "auto")
                or isinstance(plan, planning.Plan)):
            raise ValueError(
                f"unknown plan {plan!r} (one of ('manual', 'auto') or a "
                "repro.plan.Plan)")
        if on_overflow not in ("raise", "escalate"):
            raise ValueError(
                f"unknown on_overflow {on_overflow!r} "
                "(one of ('raise', 'escalate'))")
        if on_nonconverged not in (None, "warn", "raise"):
            raise ValueError(
                f"unknown on_nonconverged {on_nonconverged!r} "
                "(one of (None, 'warn', 'raise'))")
        self.backend = backend
        self.mesh = mesh
        # which knobs the caller set explicitly — they win over any plan
        # (the planner records them with source "explicit")
        self._explicit = {
            "mode": mode, "chunk_size": chunk_size,
            "use_kernel": use_kernel, "route_impl": route_impl,
            "route_batch": route_batch, "dense_threshold": dense_threshold,
        }
        self.mode = "fused" if mode is None else mode
        self.chunk_size = 64 if chunk_size is None else chunk_size
        # data-plane knobs, resolved once per engine (None = env/backend
        # default — see repro.configs.knobs) and part of every cache key:
        # a kernel-path loop and a reference-path loop are different
        # executables.
        self.use_kernel = kops.resolve_use_kernel(use_kernel)
        self.route_impl = routing.resolve_impl(route_impl)
        # how routed channels batch the query axis in run_batch compiles
        # ("union" = shared union-frontier route pass, "lane" = per-lane)
        self.route_batch = routing.resolve_batch(route_batch)
        self.dense_threshold = compose.resolve_dense_threshold(
            dense_threshold)
        # plan policy: "manual" = the resolved knobs above, verbatim;
        # "auto" = the cost-model planner decides per (program, graph
        # shape, Q); a Plan instance = use it (explicit knobs still win)
        self.plan_policy = plan
        self._planner = (planning.Planner()
                         if plan == "auto" else None)
        self._manual_plan: Optional[planning.Plan] = None
        self._cache: Dict[Tuple, runtime.CompiledSupersteps] = {}
        self.compiles = 0
        self.cache_hits = 0
        self.runs = 0
        # -- resilience policy (repro.pregel.errors) ----------------------
        # on_overflow="escalate": on ChannelOverflowError, double the
        # offending channels' capacity scales (pow2 re-bucketed at trace
        # time) and replay, up to max_retries attempts; every escalation
        # is recorded on RunResult.recovery, and the final scales are
        # memoized per planner fingerprint so repeat runs of the same
        # problem start right-sized.
        self.on_overflow = on_overflow
        self.on_nonconverged = on_nonconverged
        self.max_retries = int(max_retries)
        self._base_scales = self._norm_scales(cap_scales or {})
        # learned capacity scales: fingerprint.cache_key() -> scales dict
        self._learned: Dict[str, Dict[str, float]] = {}

    # -- introspection ----------------------------------------------------

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    def stats(self) -> Dict[str, int]:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "cached_executables": self.cache_size, "runs": self.runs}

    # -- planning ---------------------------------------------------------

    def resolve_plan(self, prog: VertexProgram, pg: PartitionedGraph,
                     num_queries: int = 0) -> planning.Plan:
        """The Plan a compile of ``prog`` on ``pg`` (Q query lanes) runs
        under, per the engine's plan policy. Explicit constructor knobs
        win under every policy; ``"auto"`` consults the cost-model
        planner (calibration probes cached on disk — never in this
        engine's compile cache, never in ``stats()``)."""
        if self.plan_policy == "auto":
            overrides = {k: getattr(self, k)
                         for k, raw in self._explicit.items()
                         if raw is not None}
            return self._planner.plan(prog, pg, num_queries=num_queries,
                                      overrides=overrides)
        if isinstance(self.plan_policy, planning.Plan):
            return self._given_plan()
        if self._manual_plan is None:
            self._manual_plan = planning.manual_plan(
                mode=self.mode, chunk_size=self.chunk_size,
                use_kernel=self.use_kernel, route_impl=self.route_impl,
                route_batch=self.route_batch,
                dense_threshold=self.dense_threshold,
                explicit=self._explicit)
        return self._manual_plan

    def _given_plan(self) -> planning.Plan:
        """A caller-supplied Plan instance, with any explicit constructor
        knobs replacing the plan's choices (explicit still wins)."""
        base = self.plan_policy
        over = {k: getattr(self, k) for k, raw in self._explicit.items()
                if raw is not None}
        if not over:
            return base
        decisions = tuple(
            planning.Decision(
                knob=d.knob, chosen=over[d.knob], source="explicit",
                candidates=d.candidates,
                reason="engine-constructor knob overrides the given plan")
            if d.knob in over else d
            for d in base.decisions)
        return dataclasses.replace(base, decisions=decisions, **over)

    # -- resilience: capacity-scale escalation ----------------------------

    @staticmethod
    def _norm_scales(scales: Dict[str, float]) -> Dict[str, float]:
        """Canonical form of a cap_scales dict: per-channel entries equal
        to the wildcard default are redundant and dropped, so an
        escalation that lands back on the default capacities keys the
        SAME cache entry as a plain run (warm executable, no recompile).
        """
        base = float(scales.get("*", 1.0))
        out: Dict[str, float] = {}
        if base != 1.0:
            out["*"] = base
        for k, v in scales.items():
            if k != "*" and float(v) != base:
                out[k] = float(v)
        return out

    def _fingerprint_key(self, prog: VertexProgram, pg: PartitionedGraph,
                         num_queries: int) -> Optional[str]:
        try:
            return features.fingerprint(
                prog, pg, num_queries=num_queries).cache_key()
        except Exception:
            return None

    def _effective_scales(self, prog: VertexProgram, pg: PartitionedGraph,
                          num_queries: int) -> Dict[str, float]:
        """Constructor cap_scales merged with any scales a previous
        escalation learned for this (program, graph shape, Q) problem —
        a repeat run starts right-sized instead of re-discovering the
        overflow one retry at a time."""
        scales = dict(self._base_scales)
        if self.on_overflow == "escalate":
            fp = self._fingerprint_key(prog, pg, num_queries)
            for k, v in self._learned.get(fp, {}).items():
                if v > scales.get(k, scales.get("*", 1.0)):
                    scales[k] = v
        return self._norm_scales(scales)

    def _remember_scales(self, prog: VertexProgram, pg: PartitionedGraph,
                         num_queries: int,
                         scales: Dict[str, float]) -> None:
        fp = self._fingerprint_key(prog, pg, num_queries)
        if fp is not None:
            self._learned[fp] = dict(scales)

    def _escalated(self, scales: Dict[str, float],
                   channels: Sequence[str]) -> Dict[str, float]:
        """Double the capacity scale of every overflowed channel (the
        trace re-buckets the scaled capacity to the next power of two).
        A global latch with no channel attribution escalates the
        wildcard — every channel grows."""
        out = dict(scales)
        for name in (list(channels) or ["*"]):
            out[name] = out.get(name, out.get("*", 1.0)) * 2.0
        return self._norm_scales(out)

    def _check_converged(self, prog: VertexProgram,
                         res: runtime.RunResult) -> None:
        if self.on_nonconverged is None or res.converged:
            return
        msg = (f"program {prog.name!r} did not converge: the max_steps "
               f"budget ({res.steps} supersteps) ran out before every "
               "vertex voted to halt")
        if self.on_nonconverged == "raise":
            raise errors.NonConvergenceError(
                msg, superstep=res.steps, result=res)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)

    # -- execution --------------------------------------------------------

    def _compile_cached(self, prog: VertexProgram, pg: PartitionedGraph,
                        state0, ms: int, co: bool, key_extra: Tuple = (),
                        num_queries: Optional[int] = None,
                        serve_chunk: Optional[int] = None,
                        cap_scales: Optional[Dict[str, float]] = None):
        """The one cache-lookup path (run, run_batch, and serve share it,
        so a new config knob lands in every key or none): return
        ``(exe, hit, plan)`` and bump the session counters. The resolved
        Plan's knob tuple IS the configuration part of the cache key — a
        planner choice and the identical hand-set choice share one
        executable.

        ``serve_chunk`` selects the serving substrate: a chunked scan at
        that chunk size with per-lane ages, regardless of the plan's
        mode (the serve loop drives dispatches itself).
        """
        plan = self.resolve_plan(prog, pg,
                                 num_queries=(num_queries or 0))
        scales = cap_scales or {}
        key = (prog, ms, co, plan.key(),
               runtime.graph_signature(pg),
               runtime.state_signature(state0),
               tuple(sorted(scales.items()))) + key_extra
        exe = self._cache.get(key)
        hit = exe is not None
        if not hit:
            # compile_supersteps/execute scrub the graph themselves, so
            # any graph with this signature replays the executable
            mode = plan.mode if serve_chunk is None else "chunked"
            chunk = plan.chunk_size if serve_chunk is None else serve_chunk
            exe = runtime.compile_supersteps(
                pg, prog.step, state0, max_steps=ms, backend=self.backend,
                mesh=self.mesh, check_overflow=co, mode=mode,
                chunk_size=chunk, channels=prog.channels,
                use_kernel=plan.use_kernel, route_impl=plan.route_impl,
                route_batch=plan.route_batch,
                dense_threshold=plan.dense_threshold,
                num_queries=num_queries,
                serve=serve_chunk is not None,
                cap_scales=scales,
            )
            self._cache[key] = exe
            self.compiles += 1
        else:
            self.cache_hits += 1
        self.runs += 1
        return exe, hit, plan

    def _stamp(self, res: runtime.RunResult, prog: VertexProgram,
               exe: runtime.CompiledSupersteps, hit: bool,
               plan: Optional[planning.Plan] = None) -> runtime.RunResult:
        if not hit:
            res.compile_time_s = exe.compile_time_s
        res.program = prog.name
        res.cache_hit = hit
        res.plan = plan
        res.engine_compiles = self.compiles
        res.engine_cache_hits = self.cache_hits
        return res

    def run(self, prog: VertexProgram, pg: PartitionedGraph, *,
            max_steps: Optional[int] = None,
            check_overflow: Optional[bool] = None,
            checkpoint_every: Optional[int] = None,
            checkpoint_dir: Optional[str] = None,
            resume: Any = None) -> runtime.RunResult:
        """Run ``prog`` on ``pg``; compile only on a cache miss.

        Returns the runtime's ``RunResult`` with ``output`` set to
        ``prog.extract(pg, state)`` and the engine/cache metadata filled
        in. ``compile_time_s`` is 0 on cache hits — the compile was paid
        by an earlier run.

        ``checkpoint_every=K`` snapshots the chunked carry into
        ``checkpoint_dir`` at the first dispatch boundary at or past
        every K supersteps (chunked mode only — see
        ``repro.pregel.checkpoint``). ``resume`` takes a checkpoint path
        or :class:`~repro.pregel.checkpoint.Checkpoint` and continues
        from that boundary, bit-identical to the uninterrupted run.

        Under ``Engine(on_overflow="escalate")`` a channel-capacity
        overflow does not kill the run: the offending channels' caps are
        re-bucketed to the next power of two and the run replays, up to
        ``max_retries`` attempts. Escalations are reported on
        ``RunResult.recovery`` and remembered per (program, graph shape)
        so the next run starts right-sized.
        """
        ms = prog.max_steps if max_steps is None else max_steps
        co = prog.check_overflow if check_overflow is None else check_overflow
        with tracing.span("job/init"):
            state0 = prog.init(pg)

        resume_carry = None
        if resume is not None:
            ckpt = (resume if isinstance(resume, ckpt_io.Checkpoint)
                    else ckpt_io.load(resume))
            ckpt.validate(prog.name, pg, ms)
            resume_carry = ckpt.carry()
        checkpoint_cb = None
        if checkpoint_every is not None:
            if checkpoint_dir is None:
                raise ValueError(
                    "checkpoint_every needs checkpoint_dir to write into")

            def checkpoint_cb(snap):
                ckpt_io.save(
                    ckpt_io.Checkpoint(
                        program=prog.name, graph=ckpt_io.graph_hash(pg),
                        max_steps=ms, **snap),
                    checkpoint_dir)

        scales = self._effective_scales(prog, pg, 0)
        recovery: List[Dict[str, Any]] = []
        attempt = 0
        while True:
            with tracing.span("job/lookup"):
                exe, hit, plan = self._compile_cached(
                    prog, pg, state0, ms, co, cap_scales=scales)
            try:
                raw = exe.execute(pg, state0,
                                  checkpoint_every=checkpoint_every,
                                  checkpoint_cb=checkpoint_cb,
                                  resume=resume_carry)
                break
            except errors.ChannelOverflowError as err:
                if self.on_overflow != "escalate" \
                        or attempt >= self.max_retries:
                    if recovery and err.result is not None:
                        err.result.recovery = recovery
                    raise
                scales = self._escalated(scales, err.channels)
                recovery.append({
                    "attempt": attempt, "superstep": err.superstep,
                    "channels": tuple(err.channels),
                    "cap_scales": dict(scales)})
                attempt += 1
        res = self._stamp(raw, prog, exe, hit, plan)
        if recovery:
            res.recovery = recovery
            self._remember_scales(prog, pg, 0, scales)
        with tracing.span("job/extract"):
            res.output = prog.extract(pg, res.state)
        self._check_converged(prog, res)
        return res

    def run_many(self, prog: VertexProgram,
                 graphs: Iterable[PartitionedGraph],
                 **kw) -> "ManyResults":
        """Run one program over many graphs; same-shape graphs after the
        first ride the cached executable. The returned list exposes the
        per-item compile-cache outcome (``.cache_hits`` / ``.hit_count``)
        so a sweep can report exactly which items replayed for free."""
        return ManyResults(self.run(prog, pg, **kw) for pg in graphs)

    def run_batch(self, prog: VertexProgram, pg: PartitionedGraph,
                  queries: Sequence[Any], *,
                  max_steps: Optional[int] = None,
                  check_overflow: Optional[bool] = None
                  ) -> runtime.RunResult:
        """Run Q query instances of ``prog`` on ``pg`` in ONE compiled
        loop (query axis vmapped inside the worker mapping, per-query
        halt voting — see ``repro.pregel.runtime``).

        ``queries`` are the per-query problem inputs fed to
        ``prog.query_init(pg, query)`` (e.g. SSSP source vertices). The
        batch is padded to the pow2 bucket cap by repeating the first
        query, so nearby batch sizes share one executable; padded lanes
        are sliced away before anything is reported.

        Returns the RunResult with per-query views: ``outputs`` (list of
        Q extracted answers — also on ``output``), ``query_steps``,
        ``query_halted``, and ``query_bytes``/``query_msgs``; the
        dict-of-int totals (``bytes_by_channel``…) cover the Q real
        queries only.
        """
        if prog.query_init is None:
            raise ValueError(
                f"program {prog.name!r} declares no query axis "
                "(VertexProgram.query_init) — it cannot be batched")
        queries = list(queries)
        q = len(queries)
        cap = bucket_queries(q)
        with tracing.span("job/init"):
            per_query = [prog.query_init(pg, query) for query in queries]
            # pad lanes reuse the first real state by reference —
            # jnp.stack copies anyway, so re-running query_init for them
            # buys nothing
            per_query += [per_query[0]] * (cap - q)
            state0 = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves, axis=1), *per_query)

        ms = prog.max_steps if max_steps is None else max_steps
        co = prog.check_overflow if check_overflow is None else check_overflow
        scales = self._effective_scales(prog, pg, cap)
        recovery: List[Dict[str, Any]] = []
        attempt = 0
        while True:
            with tracing.span("job/lookup"):
                exe, hit, plan = self._compile_cached(
                    prog, pg, state0, ms, co, key_extra=("batch", cap),
                    num_queries=cap, cap_scales=scales)
            try:
                raw = exe.execute(pg, state0, num_real_queries=q)
                break
            except errors.ChannelOverflowError as err:
                if self.on_overflow != "escalate" \
                        or attempt >= self.max_retries:
                    if recovery and err.result is not None:
                        err.result.recovery = recovery
                    raise
                scales = self._escalated(scales, err.channels)
                recovery.append({
                    "attempt": attempt, "superstep": err.superstep,
                    "channels": tuple(err.channels),
                    "qids": tuple(err.qids),
                    "cap_scales": dict(scales)})
                attempt += 1
        # the executor slices every per-query view/total/error to the Q
        # real lanes; only the raw carried state keeps the padded width
        res = self._stamp(raw, prog, exe, hit, plan)
        if recovery:
            res.recovery = recovery
            self._remember_scales(prog, pg, cap, scales)
        with tracing.span("job/extract"):
            res.outputs = [
                prog.extract(pg, jax.tree_util.tree_map(
                    lambda leaf, _qi=qi: leaf[:, _qi], res.state))
                for qi in range(q)
            ]
        res.output = res.outputs
        self._check_converged(prog, res)
        return res

    def serve(self, prog: VertexProgram, pg: PartitionedGraph,
              requests, *, num_lanes: int = 8,
              chunk_size: Optional[int] = None,
              max_steps: Optional[int] = None,
              check_overflow: Optional[bool] = None,
              faults: Optional[Sequence] = None,
              on_fault: str = "quarantine"
              ) -> serving.ServeResult:
        """Continuous-batching query service: serve a stream of queries
        through ``num_lanes`` always-on lanes, admitting from the queue
        at every chunk (dispatch) boundary as halted queries vacate
        their lanes (see ``repro.pregel.serve``).

        ``requests`` is a :class:`~repro.pregel.serve.QueryQueue`
        (arrival times in supersteps) or a plain iterable of query
        values (all arrive at t=0). Admission granularity is
        ``chunk_size`` supersteps (default: the engine's chunk size).
        One executable is compiled for the whole session — refills
        rewrite lane state in place and never re-trace — and it is
        cached under (program, graph shape, lanes, chunk), so a second
        session with the same shape replays it warm.

        Every served query is bit-identical to a solo ``Engine.run``:
        per-lane ages stand in for the step counter, so a query admitted
        at clock 400 sees step indices 0,1,2,… exactly as a fresh run
        would, and its harvested output/steps/traffic match the solo
        run's. Returns a :class:`~repro.pregel.serve.ServeResult` with
        per-query :class:`~repro.pregel.serve.QueryRecord` entries
        (qid order) and session aggregates.

        A lane that hits a channel-capacity overflow is **quarantined**
        by default (``on_fault="quarantine"``): its query is harvested
        with ``status="overflow"`` and no output, the lane is recycled,
        and every other query completes bit-identical to its solo run.
        ``on_fault="raise"`` keeps the legacy behaviour and raises
        :class:`~repro.pregel.errors.ChannelOverflowError` with the
        failed qids. ``faults`` takes deterministic
        :class:`~repro.pregel.serve.FaultSpec` injections (force an
        overflow or a step-budget exhaustion on a chosen qid at a chosen
        per-query step) for resilience drills — injected failures are
        flagged ``injected=True`` on their records.
        """
        if on_fault not in ("quarantine", "raise"):
            raise ValueError(
                f"unknown on_fault {on_fault!r} "
                "(one of ('quarantine', 'raise'))")
        if prog.query_init is None:
            raise ValueError(
                f"program {prog.name!r} declares no query axis "
                "(VertexProgram.query_init) — it cannot be served")
        if num_lanes < 1:
            raise ValueError(f"need at least one lane, got {num_lanes}")
        queue = serving.as_queue(requests)
        ms = prog.max_steps if max_steps is None else max_steps
        co = prog.check_overflow if check_overflow is None else check_overflow
        chunk = self.chunk_size if chunk_size is None else chunk_size
        if len(queue) == 0:
            return serving.ServeResult(
                program=prog.name, records=[], num_lanes=num_lanes,
                chunk_size=chunk, max_steps=ms, supersteps=0, clock=0,
                dispatches=0, wall_time_s=0.0, bytes_by_channel={},
                msgs_by_channel={}, route_batch=self.route_batch,
                cache_hit=True, engine_compiles=self.compiles,
                engine_cache_hits=self.cache_hits)
        # lane-state template: shapes/dtypes come from any query's init
        # state (all lanes are overwritten on admission; unoccupied
        # lanes are dead — halted, zero traffic, out of the union)
        template = prog.query_init(pg, queue.peek_query())
        state0 = jax.tree_util.tree_map(
            lambda leaf: jnp.repeat(leaf[:, None], num_lanes, axis=1),
            template)
        exe, hit, plan = self._compile_cached(
            prog, pg, state0, ms, co,
            key_extra=("serve", num_lanes, chunk),
            num_queries=num_lanes, serve_chunk=chunk)
        res = serving.serve_loop(exe, prog, pg, state0, queue, num_lanes,
                                 chunk, ms, co, faults=faults,
                                 on_fault=on_fault)
        res.program = prog.name
        res.route_batch = exe.route_batch
        res.plan = plan
        res.cache_hit = hit
        if not hit:
            res.compile_time_s = exe.compile_time_s
        res.engine_compiles = self.compiles
        res.engine_cache_hits = self.cache_hits
        return res


class ManyResults(List[runtime.RunResult]):
    """``Engine.run_many``'s return value: a plain result list that also
    exposes the per-item compile-cache outcome."""

    @property
    def cache_hits(self) -> List[bool]:
        return [r.cache_hit for r in self]

    @property
    def hit_count(self) -> int:
        return sum(r.cache_hit for r in self)


def run_program(prog: VertexProgram, pg: PartitionedGraph, *,
                backend: str = "vmap", mesh=None, mode: Optional[str] = None,
                chunk_size: int = 64, max_steps: Optional[int] = None,
                check_overflow: Optional[bool] = None,
                use_kernel: Optional[bool] = None,
                route_impl: Optional[str] = None,
                route_batch: Optional[str] = None) -> runtime.RunResult:
    """One-shot convenience: a throwaway single-run Engine. The legacy
    per-algorithm ``run()`` wrappers delegate here."""
    eng = Engine(backend=backend, mesh=mesh, mode=mode,
                 chunk_size=chunk_size, use_kernel=use_kernel,
                 route_impl=route_impl, route_batch=route_batch)
    return eng.run(prog, pg, max_steps=max_steps,
                   check_overflow=check_overflow)
