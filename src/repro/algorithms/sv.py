"""Shiloach-Vishkin connected components (paper §III-C, §V, Tables VI).

The showcase for channel *composition*. Three communication patterns, each
with a baseline and an optimized channel:

  1. root test + pointer jumping  (D[D[u]]):   DirectMessage 2-phase  vs
     RequestRespond channel                     [load balance]
  2. neighbor minimum  (min D[e] over Nbr[u]):  CombinedMessage per edge vs
     ScatterCombine channel                     [neighborhood traffic]
  3. remote min-update (D[D[u]] <?= t):         CombinedMessage (min)
     in all variants                            [congestion]

variants "basic" | "reqresp" | "scatter" | "both" are exactly the paper's
programs 2-5 in Table VI; "monolithic" is the Pregel baseline with one
padded message type.

variant "composed" is the paper's §V case study built on the composition
layer (``repro.core.compose``): one :class:`~repro.core.compose.Stacked`
channel bundles the request-respond pointer lookups, the min-combiner
scatter-combine neighbor minimum, the min-combined tree-merge message,
*and* a propagation-style full pointer jumping that shortcuts every tree
to a star inside the superstep (a device-side fixpoint, the same local
iteration trick the propagation channel uses) — so the composed program
needs fewer global rounds AND less traffic than any single-channel
variant, the paper's headline 2.20x composition result. Traffic is
attributed per component under namespaced keys (``sv/pointer/request``,
``sv/neighbor_min``, ``sv/merge``, ``sv/jump``, ...), and the stack
declares its full registry entry set to the runtime (the composed
VertexProgram carries ``channels=<stack>``, so the runtime skips the
eval_shape dry trace entirely).

All variants converge to D[u] = min vertex id of u's component, so their
final states are bit-identical (tests/test_compose.py relies on this).
The graph must be symmetrized.

``program(variant=...)`` builds the declarative
:class:`~repro.pregel.program.VertexProgram`; ``run`` is the thin
one-shot wrapper over :class:`repro.pregel.engine.Engine`.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.algorithms import common
from repro.core import compose
from repro.core import message as msg
from repro.core import request_respond as rr
from repro.core import scatter_combine as sc
from repro.graph.pgraph import PartitionedGraph
from repro.pregel import engine
from repro.pregel.program import VertexProgram

INF32 = jnp.iinfo(jnp.int32).max

VARIANTS = ("basic", "reqresp", "scatter", "both", "monolithic", "composed")


def composed_channels(use_kernel: Optional[bool] = None) -> compose.Stacked:
    """The §V composition: the three optimized channels plus full jumping,
    stacked under the ``sv/`` namespace with per-component attribution."""

    def neighbor_min(ctx, name, plan, vals):
        return sc.broadcast_combine(ctx, plan, vals, "min",
                                    use_kernel=use_kernel, name=name)

    return compose.stacked(
        "sv",
        pointer=compose.request_component(),
        neighbor_min=compose.Component(neighbor_min),
        merge=compose.combined_component("min"),
        jump=common.jump_component(),
    )


def _composed_step(chan: compose.Stacked):
    """One composed superstep: hook by neighbor minimum, then shortcut all
    trees to stars (full jumping) before the next global round."""

    def step(ctx, gs, state, step_idx):
        d = state["D"]

        # 1. is my parent a root?  (grand == D[u]) — request-respond.
        # After step 4's full jumping every tree is a star, so this is
        # invariantly true; the lookup is kept (rather than optimized
        # away) because it is part of the paper's composed S-V program —
        # its round and bytes are costs that program genuinely pays.
        grand, ovf1 = chan.call(ctx, "pointer", d, gs.v_mask, d,
                                capacity=ctx.n_loc)
        parent_is_root = grand == d

        # 2. minimum neighbor pointer t — min-combiner scatter-combine
        t = chan.call(ctx, "neighbor_min", gs.scatter_out, d)

        # 3. tree merging: send t to the root D[u] with a min-combiner
        cond = gs.v_mask & parent_is_root & (t < d)
        minval, got, ovf3 = chan.call(ctx, "merge", d, cond, t,
                                      capacity=ctx.n_loc)
        d1 = jnp.where(got & gs.v_mask, jnp.minimum(d, minval), d)

        # 4. full pointer jumping: D[u] <- root(u) (propagation-style
        #    device-side fixpoint — trees become stars within the step)
        d2, _ = chan.call(ctx, "jump", d1, gs.v_mask)
        d2 = jnp.where(gs.v_mask, d2, d1)

        halt = jnp.all(d2 == d)
        return {"D": d2}, halt, ovf1 | ovf3

    return step


def _init(pg):
    return {"D": pg.global_ids().astype(jnp.int32)}  # D[u] = u (pads too)


def _extract(pg, state):
    return pg.to_global(state["D"])


def program(variant: str = "both", *, max_steps: int = 200,
            use_kernel: Optional[bool] = None) -> VertexProgram:
    """S-V as a VertexProgram. Output: (n,) component labels (min member
    id) in old-id space."""
    if variant not in VARIANTS:
        raise ValueError(variant)
    meta = {"algorithm": "sv", "variant": variant}

    if variant == "composed":
        chan = composed_channels(use_kernel=use_kernel)
        return VertexProgram(
            name="sv:composed", init=_init, step=_composed_step(chan),
            extract=_extract, channels=chan, max_steps=max_steps, meta=meta,
        )

    use_rr = variant in ("reqresp", "both")
    use_sc = variant in ("scatter", "both")
    monolithic = variant == "monolithic"

    def ask(ctx, gs, dst_per_vertex, vals):
        """D[dst] for every local vertex, via the selected channel."""
        if use_rr:
            resp, ovf = rr.request(
                ctx, dst_per_vertex, gs.v_mask, vals, capacity=ctx.n_loc
            )
        else:
            resp, ovf = common.direct_request_respond(
                ctx, dst_per_vertex, gs.v_mask, vals
            )
        return resp, ovf

    def neighbor_min(ctx, gs, vals):
        """min over neighbors' vals, via the selected channel."""
        if use_sc:
            t = sc.broadcast_combine(ctx, gs.scatter_out, vals, "min",
                                     use_kernel=use_kernel)
            return t, jnp.asarray(False)
        raw = gs.raw_out
        if monolithic:
            # Pregel with an inapplicable global combiner: one message per
            # edge, combined only at the receiver (paper §V-A analysis).
            deliv = msg.direct_send(
                ctx, raw.dst_global, raw.mask,
                {"v": vals[raw.src_local]}, capacity=raw.e_cap,
                name="mono_message",
            )
            from repro.kernels import ops as kops
            inc = kops.segment_combine(
                jnp.where(deliv.mask, deliv.payload["v"], INF32),
                deliv.dst_local, ctx.n_loc, "min", use_kernel=False)
            return inc, deliv.overflow
        inc, got, ovf = msg.combined_send(
            ctx, raw.dst_global, raw.mask, vals[raw.src_local], "min",
            capacity=ctx.n_loc,
        )
        return jnp.where(got, inc, INF32), ovf

    def step(ctx, gs, state, step_idx):
        d = state["D"]

        # 1. is my parent a root?  (grand == D[u])
        grand, ovf1 = ask(ctx, gs, d, d)
        parent_is_root = grand == d

        # 2. minimum neighbor pointer t
        t, ovf2 = neighbor_min(ctx, gs, d)

        # 3. tree merging: send t to the root D[u] with a min-combiner
        cond = gs.v_mask & parent_is_root & (t < d)
        if monolithic:
            deliv = msg.direct_send(ctx, d, cond, {"t": t},
                                    capacity=ctx.n_loc, name="mono_message")
            from repro.kernels import ops as kops
            # receiver-side combine over unsorted delivery order: always
            # the reference path (kernel wants sorted segment ids)
            minval = kops.segment_combine(
                jnp.where(deliv.mask, deliv.payload["t"], INF32),
                deliv.dst_local, ctx.n_loc, "min", use_kernel=False)
            got = minval != INF32
            ovf3 = deliv.overflow
        else:
            minval, got, ovf3 = msg.combined_send(
                ctx, d, cond, t, "min", capacity=ctx.n_loc,
                name="merge_message"
            )
        d1 = jnp.where(got & gs.v_mask, jnp.minimum(d, minval), d)

        # 4. pointer jumping: D[u] <- D[D[u]] (one hop, reads merged values)
        grand2, ovf4 = ask(ctx, gs, d1, d1)
        d2 = jnp.where(gs.v_mask, grand2, d1)

        halt = jnp.all(d2 == d)
        overflow = ovf1 | ovf2 | ovf3 | ovf4
        return {"D": d2}, halt, overflow

    return VertexProgram(
        name=f"sv:{variant}", init=_init, step=step, extract=_extract,
        max_steps=max_steps, meta=meta,
    )


def run(pg: PartitionedGraph, variant: str = "both", max_steps: int = 200,
        backend: str = "vmap", mesh=None,
        use_kernel: Optional[bool] = None,
        mode=None, chunk_size: int = 64, route_impl=None):
    prog = program(variant=variant, max_steps=max_steps,
                   use_kernel=use_kernel)
    res = engine.run_program(prog, pg, backend=backend, mesh=mesh, mode=mode,
                             chunk_size=chunk_size, route_impl=route_impl)
    return res.output, res
