"""Channel composition layer (paper §V).

The paper's central claim is that optimizations become *composable* once
they are expressed as channels: the S-V case study (§V, Table VI) stacks
the request-respond, scatter-combine and combiner optimizations to beat
the best prior implementation by 2.20x. This module is the layer that
makes such stacks first-class objects instead of ad-hoc step-function
code:

  - ``Stacked`` — a named bundle of channel components. Every component's
    traffic is accounted under a *namespaced* stat key
    (``<stack>/<component>[/<sub>]``), so a composed run attributes bytes
    and messages to each constituent optimization, and the whole stack
    contributes one predeclarable ``ChannelRegistry`` entry set
    (``channel_names()`` plugs straight into ``run_supersteps(channels=)``).
  - ``fused_exchange`` — merges several *independent* planned exchanges
    into one collective round: all send buffers of one dtype share a
    single tiled ``all_to_all`` instead of one collective per channel.
  - ``switch_by_density`` — runs two channel implementations of the same
    logical exchange (a dense broadcast and a sparse push, say) and
    selects by a worker-uniform density threshold. Under the static-shape
    SPMD tracing model both branches are traced and executed every
    superstep (the registry contract requires channels to be traced
    unconditionally); the selector decides which *result* is used and
    which branch's *traffic* is accounted — consistent with how this
    library counts logical messages everywhere (see ``propagation``).
    ``density_adaptive_combine`` is the canonical instance: the same
    logical neighborhood combine as a *planned* positional
    scatter-combine (dense frontiers) vs a *routed* compact
    combined-message push (sparse frontiers), decided per superstep by
    live frontier density from the loop carry.

Composition never changes a channel's semantics: every combinator is a
pure function over the same per-shard arrays, so composed programs run
unchanged under the ``host``, ``fused`` and ``chunked`` execution modes.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs import knobs
from repro.core.channel import TRAFFIC_DTYPE, ChannelContext, key_under
from repro.pregel import tracing

#: the density-switch threshold knob (explicit > dense_threshold_scope >
#: REPRO_DENSE_THRESHOLD > 0.1): the frontier fraction at or above which
#: :func:`density_adaptive_combine` takes the planned dense broadcast.
#: ``Engine`` threads its planner-chosen threshold through the scope at
#: compile time, exactly like the use_kernel/route knobs.
DENSE_THRESHOLD = knobs.Knob(
    "dense_threshold", env="REPRO_DENSE_THRESHOLD", default=0.1,
    parse=float, coerce=float)


def resolve_dense_threshold(threshold: Optional[float] = None) -> float:
    """The density-switch threshold for a call site (explicit > scope >
    env > 0.1 — see ``repro.configs.knobs``)."""
    return DENSE_THRESHOLD.resolve(threshold)


def dense_threshold_scope(threshold: Optional[float]):
    """Pin the density-switch threshold for every adaptive combine under
    the scope (trace-time: wrap the compile, not the execution)."""
    return DENSE_THRESHOLD.scope(threshold)


# ---------------------------------------------------------------------------
# scoped accounting: child contexts whose stats fold back, namespaced
# ---------------------------------------------------------------------------


def child_context(ctx: ChannelContext, prefix: str = "") -> ChannelContext:
    """An *open* child context (no registry) sharing ctx's topology.

    Channels called with the child accumulate stats locally; fold them
    into the parent with :func:`merge_child`. Used wherever a combinator
    needs to rename or mask a component's traffic before it reaches the
    parent's (possibly registered, fixed-key) accounting.

    ``prefix`` (the name the child's stats will be merged under) composes
    the namespace so cap-scale lookups inside the child resolve the same
    full channel names the parent registry records; the engine's
    ``cap_scales`` ride along.
    """
    sub = ChannelContext(ctx.axis, ctx.num_workers, ctx.n_loc)
    sub.cap_scales = ctx.cap_scales
    sub.route_cap = ctx.route_cap
    sub.name_prefix = ctx.full_name(prefix) if prefix else ctx.name_prefix
    return sub


def merge_child(
    ctx: ChannelContext,
    child: ChannelContext,
    prefix: str = "",
    select=None,
) -> None:
    """Fold a child's stats into ``ctx`` under ``prefix/<key>``.

    select: optional 0/1 scalar (traced OK) multiplied into every counter
    — how :func:`switch_by_density` accounts only the chosen branch.
    """
    sel = None if select is None else jnp.asarray(select, TRAFFIC_DTYPE)
    for key in child.stats_bytes:
        name = f"{prefix}/{key}" if prefix else key
        nb, nm = child.stats_bytes[key], child.stats_msgs[key]
        if sel is not None:
            nb, nm = nb * sel, nm * sel
        ctx.add_traffic(name, nb, nm)
    for key in child.stats_ovf:
        name = f"{prefix}/{key}" if prefix else key
        ovf = child.stats_ovf[key]
        if sel is not None:
            # the unselected branch of a density switch must not latch
            ovf = jnp.logical_and(ovf, sel != 0)
        ctx.add_overflow(name, ovf)


@contextlib.contextmanager
def scoped(ctx: ChannelContext, prefix: str, select=None):
    """``with scoped(ctx, "sv/jump") as sub:`` — namespaced accounting."""
    sub = child_context(ctx, prefix)
    yield sub
    merge_child(ctx, sub, prefix, select)


# ---------------------------------------------------------------------------
# Stacked: a named, declarable bundle of channel components
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Component:
    """One constituent channel of a :class:`Stacked` composition.

    fn: ``fn(ctx, name, *args, **kw)`` — a closure over a channel call
      that forwards ``name`` as the channel's stat-key name.
    stats: the stat-key *suffixes* the channel contributes under its name
      — ``()`` for single-key channels (the bare name), or e.g.
      ``("request", "respond")`` for the request-respond channel.
    """

    fn: Callable
    stats: Tuple[str, ...] = ()

    def names_under(self, name: str) -> Tuple[str, ...]:
        if not self.stats:
            return (name,)
        return tuple(f"{name}/{s}" for s in self.stats)


class Stacked:
    """A composition of channels with per-component traffic attribution.

    Calling ``stack.call(ctx, key, *args)`` invokes component ``key`` with
    the namespaced stat name ``<stack.name>/<key>``; all components
    together form one fixed registry entry set (``channel_names()``),
    which ``run_supersteps(channels=stack)`` validates against the dry
    trace. This is the object the paper's §V case study builds for S-V.
    """

    def __init__(self, name: str, components: Dict[str, Component]):
        self.name = name
        self.components = dict(components)

    def call(self, ctx: ChannelContext, key: str, *args, **kw):
        comp = self.components[key]
        name = f"{self.name}/{key}"
        with tracing.channel(ctx.full_name(name)):
            return comp.fn(ctx, name, *args, **kw)

    __call__ = call

    def channel_names(self) -> Tuple[str, ...]:
        names: List[str] = []
        for key, comp in self.components.items():
            names.extend(comp.names_under(f"{self.name}/{key}"))
        return tuple(sorted(names))


def stacked(name: str, **components: Component) -> Stacked:
    """Sugar: ``stacked("sv", pointer=Component(...), ...)``."""
    return Stacked(name, components)


def request_component() -> Component:
    """The request-respond channel as a stack component: args
    ``(dst, valid, vals, capacity)``, stats ``request``/``respond``."""

    def fn(ctx, name, dst, valid, vals, capacity):
        from repro.core import request_respond as rr

        return rr.request(ctx, dst, valid, vals, capacity=capacity,
                          name=name)

    return Component(fn, stats=("request", "respond"))


def combined_component(combiner) -> Component:
    """A CombinedMessage send as a stack component: args
    ``(dst, valid, vals, capacity)``."""

    def fn(ctx, name, dst, valid, vals, capacity):
        from repro.core import message as msg

        return msg.combined_send(ctx, dst, valid, vals, combiner,
                                 capacity=capacity, name=name)

    return Component(fn)


def channel_names_of(channels) -> Tuple[str, ...]:
    """Normalize a ``channels=`` declaration: a single name, a composed
    channel (anything with ``channel_names()``), or a mixed sequence."""
    if isinstance(channels, str):
        return (channels,)
    if hasattr(channels, "channel_names"):
        return tuple(channels.channel_names())
    names: List[str] = []
    for c in channels:
        if hasattr(c, "channel_names"):
            names.extend(c.channel_names())
        else:
            names.append(c)
    return tuple(names)


# ---------------------------------------------------------------------------
# fused_exchange: several independent exchanges, one collective round
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PlannedExchange:
    """A channel exchange split at the collective boundary.

    ``payload`` holds the ready-to-send buffers — a pytree of
    ``(W, C, ...)`` arrays where row ``p`` is the block destined to peer
    ``p`` (the shape every channel in this library packs to).
    ``finish(recv)`` consumes the identically-shaped received pytree and
    produces the channel's result. ``nbytes``/``nmsgs`` is the remote
    traffic this exchange accounts under ``name``.
    """

    name: str
    payload: Any
    finish: Callable[[Any], Any]
    nbytes: Any
    nmsgs: Any


def fused_exchange(ctx: ChannelContext, parts: Sequence[PlannedExchange]) -> list:
    """Execute several planned exchanges in one collective round.

    All send buffers of equal dtype are flattened to ``(W, -1)``,
    concatenated, and exchanged with a *single* tiled ``all_to_all``
    (one collective per distinct dtype instead of one per channel); the
    received block is split back and each part's ``finish`` runs on its
    own slice. Results come back in ``parts`` order. Each part's traffic
    is accounted under its own name — fusing the wire round never blurs
    the per-channel attribution.

    The parts must be data-independent (none may consume another's
    result) — the same condition under which the paper may merge channel
    exchanges into one message round.
    """
    if not parts:
        return []
    flat_parts = []
    for part in parts:
        leaves, treedef = jax.tree_util.tree_flatten(part.payload)
        flat_parts.append((leaves, treedef))

    # group leaves across parts by dtype: one collective per dtype
    groups: Dict[Any, List[Tuple[int, int, jax.Array]]] = {}
    for pi, (leaves, _) in enumerate(flat_parts):
        for li, leaf in enumerate(leaves):
            groups.setdefault(jnp.dtype(leaf.dtype), []).append((pi, li, leaf))

    recv_leaves: List[List[Optional[jax.Array]]] = [
        [None] * len(leaves) for leaves, _ in flat_parts
    ]
    # one part owns its collective; a merged round is shared by its parts
    owner = (tracing.channel(ctx.full_name(parts[0].name))
             if len(parts) == 1 else contextlib.nullcontext())
    with owner, tracing.scope(tracing.EXCHANGE):
        for items in groups.values():
            w = items[0][2].shape[0]
            cols = [leaf.reshape(w, -1) for _, _, leaf in items]
            widths = [col.shape[1] for col in cols]
            merged = (cols[0] if len(cols) == 1
                      else jnp.concatenate(cols, axis=1))
            back = jax.lax.all_to_all(merged, ctx.axis, 0, 0, tiled=True)
            off = 0
            for (pi, li, leaf), width in zip(items, widths):
                recv_leaves[pi][li] = back[:, off: off + width].reshape(
                    leaf.shape)
                off += width

    results = []
    for pi, part in enumerate(parts):
        ctx.add_traffic(part.name, part.nbytes, part.nmsgs)
        recv = jax.tree_util.tree_unflatten(flat_parts[pi][1], recv_leaves[pi])
        with tracing.channel(ctx.full_name(part.name)):
            results.append(part.finish(recv))
    return results


# ---------------------------------------------------------------------------
# switch_by_density: density-directed choice between two channel impls
# ---------------------------------------------------------------------------


def global_fraction(ctx: ChannelContext, local_count, local_total) -> jax.Array:
    """Worker-uniform fraction ``sum(count) / sum(total)`` (f32 scalar)."""
    num = jax.lax.psum(jnp.asarray(local_count, jnp.float32), ctx.axis)
    den = jax.lax.psum(jnp.asarray(local_total, jnp.float32), ctx.axis)
    return num / jnp.maximum(den, 1.0)


def switch_by_density(
    ctx: ChannelContext,
    name: str,
    density,
    threshold: Optional[float],
    dense_fn: Callable[[ChannelContext], Any],
    sparse_fn: Callable[[ChannelContext], Any],
):
    """Select between two implementations of one logical exchange.

    ``dense_fn(sub_ctx)`` and ``sparse_fn(sub_ctx)`` must return results
    of identical pytree structure; ``density`` must be worker-uniform
    (use :func:`global_fraction`). Returns ``(result, use_dense)`` where
    ``result`` is the dense result when ``density >= threshold`` and the
    sparse one otherwise.

    Both branches are traced and executed unconditionally (the registry
    contract — and ``lax.cond`` branches could not mutate the trace-time
    stats dict anyway); only the chosen branch's traffic is accounted,
    under ``<name>/dense/...`` and ``<name>/sparse/...``, mirroring the
    logical-message accounting used throughout this library.

    ``threshold=None`` resolves through the :data:`DENSE_THRESHOLD` knob
    at trace time (scope > env > 0.1) — the planner's entry point.
    """
    use_dense = jnp.asarray(density) >= resolve_dense_threshold(threshold)
    d_ctx = child_context(ctx, f"{name}/dense")
    s_ctx = child_context(ctx, f"{name}/sparse")
    d_out = dense_fn(d_ctx)
    s_out = sparse_fn(s_ctx)
    sel = use_dense.astype(TRAFFIC_DTYPE)
    merge_child(ctx, d_ctx, f"{name}/dense", select=sel)
    merge_child(ctx, s_ctx, f"{name}/sparse", select=1 - sel)
    result = jax.tree_util.tree_map(
        lambda a, b: jnp.where(use_dense, a, b), d_out, s_out
    )
    return result, use_dense


def density_adaptive_combine(
    ctx: ChannelContext,
    name: str,
    density,
    threshold: Optional[float],
    *,
    plan,
    dense_vals: jax.Array,
    dst: jax.Array,
    valid: jax.Array,
    sparse_vals: jax.Array,
    combiner,
    capacity: int,
    use_kernel=None,
    edge_transform=None,
):
    """Routed-vs-planned exchange for one logical neighborhood combine,
    selected by live frontier density.

    The two implementations of the same logical exchange are the two ends
    of the data plane: the *planned* positional ScatterCombine broadcast
    (``plan`` + ``dense_vals`` — static routing, no ids on the wire, cost
    independent of the frontier) and the *routed* CombinedMessage push
    (``dst``/``valid``/``sparse_vals`` — one-pass bucket routing, ids on
    the wire but only active messages travel). ``density`` must be
    worker-uniform and should come from the loop carry (e.g.
    ``global_fraction(ctx, active & v_mask, v_mask)``) — the decision
    tracks the frontier *live*, per superstep, inside the fused loop.

    Returns ``(combined (n_loc,[D]) — combiner identity where nothing
    arrived, overflow, use_dense)``; traffic lands under
    ``<name>/dense/scatter_combine`` vs ``<name>/sparse/combined_message``.
    """

    def dense(sub):
        from repro.core import scatter_combine as sc

        out = sc.broadcast_combine(
            sub, plan, dense_vals, combiner,
            edge_transform=edge_transform, use_kernel=use_kernel,
        )
        return out, jnp.asarray(False)

    def sparse(sub):
        from repro.core import message as msg

        out, _, ovf = msg.combined_send(
            sub, dst, valid, sparse_vals, combiner, capacity=capacity,
            use_kernel=use_kernel,
        )
        return out, ovf

    (result, overflow), use_dense = switch_by_density(
        ctx, name, density, threshold, dense, sparse
    )
    return result, overflow, use_dense


# ---------------------------------------------------------------------------
# stat helpers for namespaced keys
# ---------------------------------------------------------------------------


def group_stats(stats: Dict[str, int]) -> Dict[str, int]:
    """Collapse namespaced stats to per-top-level-prefix totals."""
    out: Dict[str, int] = {}
    for key, val in stats.items():
        top = key.split("/", 1)[0]
        out[top] = out.get(top, 0) + val
    return out


def stats_under(stats: Dict[str, int], prefix: str) -> Dict[str, int]:
    """The subset of ``stats`` belonging to ``prefix`` (exact or nested)."""
    return {k: v for k, v in stats.items() if key_under(k, prefix)}
