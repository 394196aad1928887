"""Scatter-Combine channel (paper §IV-C1).

The *static messaging pattern*: every vertex sends a value to all of its
neighbors, every superstep, regardless of state. The channel preprocesses
the edges once (sorted by destination, sender-side dedup to one slot per
unique destination per worker, positional receive tables) so that each
superstep is: gather → sorted-segment combine (Pallas kernel on TPU) →
one all_to_all with **no vertex ids on the wire** → receive-side combine.

The exchange is exposed in two forms: :func:`broadcast_combine` performs
the whole superstep, while :func:`plan_broadcast_combine` returns a
``PlannedExchange`` split at the collective boundary so the composition
layer (``repro.core.compose.fused_exchange``, paper §V) can merge several
independent channels' exchanges into one collective round.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import combiners as cb
from repro.core import compose
from repro.core.channel import TRAFFIC_DTYPE, ChannelContext
from repro.graph.pgraph import ScatterPlan
from repro.kernels import ops as kops
from repro.pregel import tracing


@tracing.channel_call
def plan_broadcast_combine(
    ctx: ChannelContext,
    plan: ScatterPlan,
    vertex_vals: jax.Array,
    combiner,
    *,
    edge_transform: Optional[Callable] = None,
    use_kernel: Optional[bool] = None,
    name: str = "scatter_combine",
) -> compose.PlannedExchange:
    """Stage one scatter-combine superstep up to (but not including) the
    collective; see :func:`broadcast_combine` for argument semantics.

    Returns a ``PlannedExchange`` whose payload is the packed positional
    ``(W, C, D)`` send buffer and whose ``finish`` performs the
    receive-side combine. Execute it — alone or merged with other
    channels' planned exchanges — via ``compose.fused_exchange``.
    """
    combiner = cb.get(combiner)
    w, c = ctx.num_workers, plan.slot_cap
    squeeze = vertex_vals.ndim == 1
    vals = vertex_vals[:, None] if squeeze else vertex_vals
    d = vals.shape[-1]
    ident = combiner.ident_for(vals.dtype)

    # 1. per-edge values (gather by local src; padded edges dropped via seg
    # id). Mirrored plans (partition_graph(mirror_threshold=...)) extend
    # the gather index space with every worker's exported-hub values:
    # index n_loc + owner * hub_cap + hub_rank reads the mirror of a
    # remote hub. The mirror->master refresh is the *static* special case
    # of the RequestRespond channel — the request ids (each owner's
    # hub_local table) are precomputed into the plan and the respond phase
    # is positional, so the round trip collapses to one all_gather of the
    # (hub_cap, D) hub-value tables per superstep. Mirror traffic is
    # charged below under this channel's own stat key.
    mirror_msgs = jnp.zeros((), TRAFFIC_DTYPE)
    if plan.hub_cap:
        exported = plan.hub_local < ctx.n_loc  # (hub_cap,) real slots
        safe = jnp.minimum(plan.hub_local, ctx.n_loc - 1)
        mine = jnp.where(exported[:, None], vals[safe], ident)
        with tracing.scope(tracing.EXCHANGE):
            hubs = jax.lax.all_gather(mine, ctx.axis)  # (W, hub_cap, D)
            vals_ext = jnp.concatenate([vals, hubs.reshape(-1, d)], axis=0)
        mirror_msgs = (jnp.sum(exported) * (w - 1)).astype(TRAFFIC_DTYPE)
    else:
        vals_ext = vals
    per_edge = vals_ext[plan.edge_src]
    if edge_transform is not None:
        per_edge = edge_transform(per_edge, plan.edge_w)

    # 2. sender-side combine: one value per unique destination (sorted
    # ids). The kernel path rides the plan's autotuned block sizes and
    # precomputed work list (graph/pgraph.py) instead of deriving a
    # worst-case one on device.
    kernel_kw = {}
    if plan.item_block is not None:
        kernel_kw = dict(
            block_rows=plan.block_rows,
            block_edges=plan.block_edges,
            work_list=(plan.item_block, plan.item_chunk),
        )
    u_vals = kops.segment_combine(
        per_edge, plan.edge_seg, plan.u_cap, combiner,
        use_kernel=use_kernel, assume_sorted=True, **kernel_kw,
    )

    # 3. positional pack (payload only — the routing is static)
    with tracing.scope(tracing.ROUTE):
        buf = jnp.full((w * c + 1, d), ident, vals.dtype)
        buf = buf.at[plan.pack_slot].set(u_vals, mode="drop")
        send = buf[: w * c].reshape(w, c, d)

    # 4. (deferred) receive-side combine into dense per-vertex values
    def finish(recv):
        out = kops.segment_combine(
            recv["v"].reshape(w * c, d), plan.recv_local.reshape(-1),
            ctx.n_loc, combiner, use_kernel=False,
        )
        return out[:, 0] if squeeze else out

    me = ctx.me()
    remote = (plan.send_count.sum() - plan.send_count[me]).astype(TRAFFIC_DTYPE)
    remote = remote + mirror_msgs  # hub broadcast crosses (W-1) boundaries
    return compose.PlannedExchange(
        name=name,
        payload={"v": send},
        finish=finish,
        nbytes=remote * d * jnp.dtype(vals.dtype).itemsize,
        nmsgs=remote,
    )


def broadcast_combine(
    ctx: ChannelContext,
    plan: ScatterPlan,
    vertex_vals: jax.Array,
    combiner,
    *,
    edge_transform: Optional[Callable] = None,
    use_kernel: Optional[bool] = None,
    name: str = "scatter_combine",
) -> jax.Array:
    """One scatter-combine superstep.

    Args:
      plan: per-shard ScatterPlan (leading W axis already mapped away).
      vertex_vals: (n_loc,) or (n_loc, D) per-vertex value to broadcast.
      combiner: Combiner (receiver gets combine over in-neighbors).
      edge_transform: optional fn(per_edge_vals, edge_w) -> per_edge_vals
        (e.g. dist + weight for SSSP over a weighted plan).
    Returns:
      (n_loc,) or (n_loc, D) combined incoming value per local vertex
      (combiner identity where nothing arrived).
    """
    planned = plan_broadcast_combine(
        ctx, plan, vertex_vals, combiner,
        edge_transform=edge_transform, use_kernel=use_kernel, name=name,
    )
    (out,) = compose.fused_exchange(ctx, [planned])
    return out
