"""The (rows, 128) lane layout shared by the frame kernels.

A TPU vector register is 8 sublanes × 128 lanes, and Mosaic blocks an
array only where the last two block dimensions are multiples of (8, 128) or
span the whole array.  A 1-D column therefore enters a kernel as a
``(rows, 128)`` matrix — padded with a neutral fill to whole row tiles — and
is blocked as ``(tile_rows, 128)`` with ``tile_rows`` a multiple of 8.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

LANES = 128
SUBLANES = 8


def row_layout(n: int, tile_rows: int) -> Tuple[int, int]:
    """``(rows, tile_rows)`` for an ``n``-element column: the row tile is
    the requested one, shrunk to the column's own size, a multiple of 8;
    ``rows`` is padded up to whole tiles."""
    if tile_rows % SUBLANES:
        raise ValueError(f"tile_rows={tile_rows} is not a multiple of {SUBLANES}")
    rows = -(-max(int(n), 1) // LANES)
    rows = -(-rows // SUBLANES) * SUBLANES
    tr = min(tile_rows, rows)
    return -(-rows // tr) * tr, tr


def to_lanes(x: jnp.ndarray, rows: int, fill) -> jnp.ndarray:
    """Pad a 1-D array with ``fill`` to ``rows * 128`` and lay it out."""
    pad = rows * LANES - x.shape[0]
    if pad:
        x = jnp.pad(x, (0, pad), constant_values=fill)
    return x.reshape(rows, LANES)


# In-kernel reductions of a (rows, 128) tile to a (1, 1) vector: the sublane
# axis first, then the lanes — both forms Mosaic lowers.
def tile_sum(a):
    return jnp.sum(jnp.sum(a, axis=0, keepdims=True), axis=1, keepdims=True)


def tile_min(a):
    return jnp.min(jnp.min(a, axis=0, keepdims=True), axis=1, keepdims=True)


def tile_max(a):
    return jnp.max(jnp.max(a, axis=0, keepdims=True), axis=1, keepdims=True)
