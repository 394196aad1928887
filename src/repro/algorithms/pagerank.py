"""PageRank (paper Fig. 1 / Table V top).

Variants:
  - "basic":    CombinedMessage channel (per-superstep sort-based routing,
                ids on the wire) — the standard-channel Fig. 1 program.
  - "scatter":  ScatterCombine channel (static plan, no ids) — the paper's
                one-line optimization switch.
  - "personal": personalized PageRank — the teleport (and sink) mass goes
                to a single source vertex instead of the uniform vector,
                over the same ScatterCombine channel. The source is the
                program's *query axis* (``query_init``):
                ``Engine.run_batch(prog, pg, sources)`` scores Q
                personalization vectors in one compiled batched loop —
                the per-user-ranking serving shape.

``program(variant=...)`` builds the declarative
:class:`~repro.pregel.program.VertexProgram`; ``run`` is the thin
one-shot wrapper over :class:`repro.pregel.engine.Engine`.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core import aggregator as agg
from repro.core import message as msg
from repro.core import scatter_combine as sc
from repro.graph.pgraph import PartitionedGraph
from repro.pregel import engine
from repro.pregel.program import VertexProgram

VARIANTS = ("basic", "scatter", "personal")


def program(variant: str = "scatter", *, iters: int = 30,
            damping: float = 0.85, source: int = 0,
            use_kernel: Optional[bool] = None) -> VertexProgram:
    """PageRank as a VertexProgram. Output: (n,) ranks in old-id space."""
    if variant not in VARIANTS:
        raise ValueError(variant)

    if variant == "personal":
        return _personal(iters=iters, damping=damping, source=source,
                         use_kernel=use_kernel)

    def init(pg):
        return {"pr": jnp.where(pg.v_mask, 1.0 / jnp.float32(pg.n), 0.0)}

    def step(ctx, gs, state, step_idx):
        # gs.n is a static field of the graph shard — the program stays
        # graph-agnostic (n is baked per compiled shape, not per program)
        n = jnp.float32(gs.n)
        pr = state["pr"]
        deg = jnp.maximum(gs.deg_out, 1).astype(jnp.float32)
        contrib = jnp.where(gs.deg_out > 0, pr / deg, 0.0)
        overflow = jnp.asarray(False)
        if variant == "scatter":
            incoming = sc.broadcast_combine(
                ctx, gs.scatter_out, contrib, "sum", use_kernel=use_kernel
            )
        else:
            raw = gs.raw_out
            incoming, _, overflow = msg.combined_send(
                ctx,
                raw.dst_global,
                raw.mask,
                contrib[raw.src_local],
                "sum",
                capacity=ctx.edge_capacity(ctx.n_loc),
            )
        sink = agg.aggregate(
            ctx, jnp.where((gs.deg_out == 0) & gs.v_mask, pr, 0.0), "sum"
        )
        new_pr = jnp.where(
            gs.v_mask, (1 - damping) / n + damping * (incoming + sink / n), 0.0
        )
        return {"pr": new_pr}, step_idx >= iters - 1, overflow

    def extract(pg, state):
        return pg.to_global(state["pr"])

    return VertexProgram(
        name=f"pagerank:{variant}", init=init, step=step, extract=extract,
        max_steps=iters,
        meta={"algorithm": "pagerank", "variant": variant, "iters": iters,
              "damping": damping},
    )


def _personal(*, iters: int, damping: float, source: int,
              use_kernel: Optional[bool]) -> VertexProgram:
    """Personalized PageRank: teleport and sink mass concentrate on one
    source vertex. The source rides the *state* as a per-worker scalar
    (not a closure constant), so the step stays graph- and
    query-agnostic — exactly what lets run_batch vmap it over sources."""

    def query_init(pg, src_old):
        src_new = int(pg.new_of_old.arr[src_old])
        ids = pg.global_ids()
        e = ((ids == src_new) & pg.v_mask).astype(jnp.float32)
        return {"pr": e,
                "src": jnp.full((pg.num_workers,), src_new, jnp.int32)}

    def init(pg):
        return query_init(pg, source)

    def step(ctx, gs, state, step_idx):
        pr, src = state["pr"], state["src"]
        ids = (ctx.me() * ctx.n_loc
               + jnp.arange(ctx.n_loc, dtype=jnp.int32))
        e = ((ids == src) & gs.v_mask).astype(jnp.float32)
        deg = jnp.maximum(gs.deg_out, 1).astype(jnp.float32)
        contrib = jnp.where(gs.deg_out > 0, pr / deg, 0.0)
        incoming = sc.broadcast_combine(
            ctx, gs.scatter_out, contrib, "sum", use_kernel=use_kernel
        )
        sink = agg.aggregate(
            ctx, jnp.where((gs.deg_out == 0) & gs.v_mask, pr, 0.0), "sum"
        )
        new_pr = jnp.where(
            gs.v_mask, (1 - damping) * e + damping * (incoming + sink * e),
            0.0,
        )
        return {"pr": new_pr, "src": src}, step_idx >= iters - 1

    def extract(pg, state):
        return pg.to_global(state["pr"])

    return VertexProgram(
        name="pagerank:personal", init=init, step=step, extract=extract,
        query_init=query_init, max_steps=iters,
        meta={"algorithm": "pagerank", "variant": "personal",
              "iters": iters, "damping": damping, "source": source},
    )


def run(pg: PartitionedGraph, iters: int = 30, variant: str = "scatter",
        damping: float = 0.85, source: int = 0, backend: str = "vmap",
        mesh=None, use_kernel: Optional[bool] = None, mode=None,
        chunk_size: int = 64):
    prog = program(variant=variant, iters=iters, damping=damping,
                   source=source, use_kernel=use_kernel)
    res = engine.run_program(prog, pg, backend=backend, mesh=mesh, mode=mode,
                             chunk_size=chunk_size)
    return res.output, res
