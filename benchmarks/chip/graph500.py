"""The benchmark's own Graph500 Kronecker generator.

Follows the Graph500 specification (v3, section 3, and its reference
``kronecker_generator``): ``edge_factor * 2**scale`` edges, each placed by
``scale`` independent quadrant choices with initiator A=0.57, B=C=0.19
(D=0.05), then every vertex label permuted by a random permutation drawn
from the seed. Kernel 1 of the specification treats the edge list as
undirected; here it is symmetrized, and self-loops and duplicate edges are
removed (the specification allows both; the configurations list the
removal under ``assumed``). Search keys are drawn only from vertices of
degree at least one, as the specification requires.

The random bits come from ``jax.random`` (threefry), drawn on JAX's CPU
device in blocks of ``BLOCK`` edges, so generation touches no accelerator
memory; the symmetrize-and-deduplicate pass runs in numpy. A configuration's
graph is a fixed data set, made from the configuration's own seed
(``generators/graph500_kronecker.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from graphs import Graph

#: Graph500 initiator probabilities (A, B, C); D = 1 - A - B - C
INITIATOR = (0.57, 0.19, 0.19)
#: edges drawn per device call
BLOCK = 1 << 20


def root_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative whole seed, 64 bits and beyond."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key


@functools.partial(jax.jit, static_argnames=("scale", "count", "initiator"))
def _kronecker_block(key, *, scale: int, count: int, initiator: tuple):
    """``count`` Kronecker edges as (src, dst) int32, before permutation."""
    a, b, c = initiator
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = jnp.zeros((count,), jnp.int32)
    dst = jnp.zeros((count,), jnp.int32)
    for level in range(scale):
        k_i, k_j = jax.random.split(jax.random.fold_in(key, level))
        ii = jax.random.uniform(k_i, (count,)) > ab
        thresh = jnp.where(ii, c_norm, a_norm)
        jj = jax.random.uniform(k_j, (count,)) > thresh
        src = src | (ii.astype(jnp.int32) << level)
        dst = dst | (jj.astype(jnp.int32) << level)
    return src, dst


@functools.partial(jax.jit, static_argnames=("n",))
def _permutation(key, *, n: int):
    return jax.random.permutation(key, n).astype(jnp.int32)


def generate(scale: int, edge_factor: int, seed: int,
             initiator: tuple = INITIATOR) -> Graph:
    """The Graph500 graph of ``(scale, edge_factor)`` for ``seed``, with
    initiator ``(A, B, C)``."""
    if not 1 <= scale <= 30:
        raise ValueError(f"scale must be in [1, 30], got {scale}")
    a, b, c = (float(x) for x in initiator)
    if min(a, b, c, 1.0 - a - b - c) < 0:
        raise ValueError(f"not an initiator: {initiator}")
    with jax.default_device(jax.devices("cpu")[0]):
        return _generate(scale, edge_factor, seed, (a, b, c))


def _generate(scale: int, edge_factor: int, seed: int,
              initiator: tuple) -> Graph:
    n = 1 << scale
    m = edge_factor * n
    key = jax.random.fold_in(root_key(seed), 0)
    perm = np.asarray(_permutation(jax.random.fold_in(key, 0), n=n))
    edge_key = jax.random.fold_in(key, 1)
    keys = np.empty(2 * m, np.int64)
    for start in range(0, m, BLOCK):
        count = min(BLOCK, m - start)
        s, d = _kronecker_block(jax.random.fold_in(edge_key, start // BLOCK),
                                scale=scale, count=BLOCK,
                                initiator=initiator)
        s = perm[np.asarray(s)[:count]].astype(np.int64)
        d = perm[np.asarray(d)[:count]].astype(np.int64)
        keys[start:start + count] = (s << scale) | d
        keys[m + start:m + start + count] = (d << scale) | s
    keys = np.unique(keys)  # sorted: (src, dst) order, duplicates gone
    src = (keys >> scale).astype(np.int32)
    dst = (keys & (n - 1)).astype(np.int32)
    keep = src != dst
    return Graph(n, src[keep], dst[keep])
