"""Aggregator channel (paper Table I): global reduction available to every
vertex next superstep. Lowers to a single mesh collective; traffic is
O(W * payload), which we account like the paper does (one value per
worker toward the master, broadcast back).

``all_halted`` is the runtime's voting-to-halt primitive: a device-side
psum whose result feeds the fused loop condition directly — no host
involvement per superstep."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import combiners as cb
from repro.core.channel import ChannelContext
from repro.pregel import tracing


@tracing.channel_call
def aggregate(
    ctx: ChannelContext,
    values: jax.Array,
    combiner,
    valid: Optional[jax.Array] = None,
    *,
    name: str = "aggregator",
):
    """Combine `values` over all vertices of all workers.

    Args:
      values: (n_loc, ...) per-vertex contributions.
      valid: (n_loc,) mask of contributing vertices (default: all).
    Returns:
      scalar/array: the global combined value (replicated on all workers).
    """
    combiner = cb.get(combiner)
    if valid is not None:
        mask = valid.reshape(valid.shape + (1,) * (values.ndim - valid.ndim))
        values = jnp.where(mask, values, combiner.ident_for(values.dtype))
    # local reduce then cross-worker reduce
    local = values
    if combiner.name == "sum":
        local = local.sum(axis=0)
    elif combiner.name == "min":
        local = local.min(axis=0)
    elif combiner.name == "max":
        local = local.max(axis=0)
    elif combiner.name == "or":
        local = local.any(axis=0)
    elif combiner.name == "prod":
        local = local.prod(axis=0)
    else:
        red = combiner.identity_like(local[0])
        for_fn = lambda i, acc: combiner.fn(acc, local[i])
        local = jax.lax.fori_loop(0, local.shape[0], for_fn, red)
    out = combiner.psum_like(local, ctx.axis)
    per = int(jnp.dtype(values.dtype).itemsize)
    for dim in values.shape[1:]:
        per *= int(dim)
    # 2(W-1) values on the wire: gather + broadcast
    ctx.add_traffic(name, 2 * (ctx.num_workers - 1) * per, 2 * (ctx.num_workers - 1))
    return out


def all_halted(ctx: ChannelContext, local_halt) -> jax.Array:
    """Voting-to-halt: true iff every worker votes halt."""
    votes = jax.lax.psum(jnp.asarray(local_halt, jnp.int32), ctx.axis)
    return votes == ctx.num_workers
