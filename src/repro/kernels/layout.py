"""Tile layout helpers shared by the Pallas kernels.

Both kernels stream a long per-message (or per-edge) vector in chunks.
A ``(chunk, 1)`` column would occupy a whole 128-lane row per element in
HBM and VMEM, so each chunk is laid out lane-dense instead: ``(rows,
lanes)`` in element order, with ``lanes = min(chunk, 128)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def chunk_tile(chunk: int):
    """``(rows, lanes)`` layout of one chunk of ``chunk`` elements."""
    lanes = min(chunk, LANES)
    assert chunk % lanes == 0, f"chunk {chunk} is not a multiple of {lanes}"
    return chunk // lanes, lanes


def col_to_row(col):
    """(N, 1) column -> (1, N) row, exactly: a diagonal select (0
    elsewhere) summed down the sublanes — one real value per column."""
    n = col.shape[0]
    diag = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(diag, col, 0), axis=0, keepdims=True)
