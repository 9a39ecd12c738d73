"""Stream compaction (filter) for TPU.

Hardware adaptation: the CUDA idiom is warp-ballot + shared-memory scatter.
TPUs have neither.  Each 128-lane row of a ``(tile_rows, 128)`` tile is
compacted on its own: the exclusive keep-prefix-sum comes from one MXU
matmul against a strictly upper-triangular 0/1 matrix (exact: integer sums
below 128), and the kept values move to their slots with one select per
source lane — pure data movement, so every value (NaN and ±inf included)
arrives bit for bit.  The jit'd wrapper stitches the rows with per-row
counts (cheap, XLA); the O(n) data pass stays in the kernel.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .lanes import LANES, row_layout, to_lanes

DEFAULT_TILE_ROWS = 64


def _compact_kernel(x_ref, keep_ref, out_ref, *, fill: float):
    # x_ref (TR, 128) f32; keep_ref (TR, 128) f32 0/1; out_ref (TR, 128)
    shape = x_ref.shape
    keep = keep_ref[...]
    src = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    upper = (src < dst).astype(jnp.float32)
    excl = jnp.dot(keep, upper, preferred_element_type=jnp.float32)
    # slot of each kept lane within its row; -1 for dropped lanes
    slot = jnp.where(keep > 0.0, excl, -1.0)
    x = x_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1).astype(jnp.float32)
    out = jnp.full(shape, fill, jnp.float32)
    for i in range(LANES):  # source lane i → its slot in every row at once
        hit = jnp.broadcast_to(slot[:, i:i + 1], shape) == lane
        out = jnp.where(hit, jnp.broadcast_to(x[:, i:i + 1], shape), out)
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("tile_rows", "fill", "interpret"))
@obs.device_scope("filter_compact")
def filter_compact(
    x: jnp.ndarray,  # f32[n]
    keep: jnp.ndarray,  # bool[n]
    tile_rows: int = DEFAULT_TILE_ROWS,
    fill: float = 0.0,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable compaction. Returns (compacted[n] padded with ``fill``, count)."""
    n = x.shape[0]
    rows, tr = row_layout(n, tile_rows)
    keep2 = to_lanes(keep.astype(jnp.float32), rows, 0.0)
    tiles = pl.pallas_call(
        functools.partial(_compact_kernel, fill=fill),
        grid=(rows // tr,),
        in_specs=[pl.BlockSpec((tr, LANES), lambda t: (t, 0))] * 2,
        out_specs=pl.BlockSpec((tr, LANES), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(to_lanes(x.astype(jnp.float32), rows, 0.0), keep2)
    # stitch rows: global position of row r's slot j = offset[r] + j
    cnt = jnp.sum(keep2, axis=1).astype(jnp.int32)  # (rows,)
    offsets = jnp.cumsum(cnt) - cnt  # exclusive prefix
    total = jnp.sum(cnt)
    slot = jnp.arange(LANES)[None, :]
    global_pos = jnp.where(slot < cnt[:, None], offsets[:, None] + slot, n)
    out = jnp.full((n + 1,), fill, jnp.float32)
    out = out.at[global_pos.reshape(-1)].set(tiles.reshape(-1), mode="drop")
    return out[:n].astype(x.dtype), total
