"""Segment reduction (groupby-aggregate / value_counts) for TPU.

Hardware adaptation: GPU groupby kernels scatter with atomics; TPUs have no
atomics and hate random scatter.  The reduction runs as a dense bucket sweep
instead: for a ``(tile_rows, 128)`` block of rows and a block of 128
buckets (one lane each), every bucket ``b`` is a full-tile compare
``keys == b`` and a masked reduce, and the result lands in lane ``b`` of
the bucket block's (8, 128) accumulator.  No data-dependent addressing, no
scatter.  Row tiles stream through the innermost grid dimension,
accumulating into the output bucket block.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .lanes import LANES, SUBLANES, row_layout, tile_max, tile_min, tile_sum, to_lanes

DEFAULT_TILE_ROWS = 256  # 32K rows per grid step
_VREG = (SUBLANES, LANES)
_INIT = {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf}


def _segment_kernel(
    keys_ref,  # (TR, 128) int32
    vals_ref,  # (TR, 128) f32
    valid_ref,  # (TR, 128) f32, 1.0 = valid
    out_ref,  # (8, 128) f32: lane j = bucket bi*128 + j (every row alike)
    cnt_ref,  # (8, 128) f32 counts, same layout
    *,
    mode: str,
):
    bi = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.full(_VREG, _INIT[mode], jnp.float32)
        cnt_ref[...] = jnp.zeros(_VREG, jnp.float32)

    keys = keys_ref[...]
    vals = vals_ref[...]
    valid = valid_ref[...] > 0.0
    lane = jax.lax.broadcasted_iota(jnp.int32, _VREG, 1)
    base = bi * LANES

    def bucket(j, carry):
        red, cnt = carry
        hit = (keys == base + j) & valid
        sel = lane == j
        cnt = jnp.where(sel, cnt + tile_sum(jnp.where(hit, 1.0, 0.0)), cnt)
        if mode == "sum":
            red = jnp.where(sel, red + tile_sum(jnp.where(hit, vals, 0.0)), red)
        elif mode == "min":
            r = tile_min(jnp.where(hit, vals, jnp.inf))
            red = jnp.where(sel, jnp.minimum(red, r), red)
        else:
            r = tile_max(jnp.where(hit, vals, -jnp.inf))
            red = jnp.where(sel, jnp.maximum(red, r), red)
        return red, cnt

    red, cnt = jax.lax.fori_loop(0, LANES, bucket, (out_ref[...], cnt_ref[...]))
    out_ref[...] = red
    cnt_ref[...] = cnt


@functools.partial(
    jax.jit, static_argnames=("num_buckets", "mode", "tile_rows", "interpret"),
)
@obs.device_scope("segment_reduce")
def segment_reduce(
    keys: jnp.ndarray,  # int32[n]
    values: jnp.ndarray,  # f32[n]
    valid: jnp.ndarray,  # bool[n]
    num_buckets: int,
    mode: str = "sum",
    tile_rows: int = DEFAULT_TILE_ROWS,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (reduced[num_buckets], counts[num_buckets])."""
    if mode not in _INIT:
        raise ValueError(f"segment_reduce mode {mode!r}")
    rows, tr = row_layout(keys.shape[0], tile_rows)
    keys2 = to_lanes(keys.astype(jnp.int32), rows, -1)
    vals2 = to_lanes(values.astype(jnp.float32), rows, 0.0)
    valid2 = to_lanes(valid.astype(jnp.float32), rows, 0.0)
    nbb = -(-num_buckets // LANES)
    out, cnt = pl.pallas_call(
        functools.partial(_segment_kernel, mode=mode),
        grid=(nbb, rows // tr),
        in_specs=[pl.BlockSpec((tr, LANES), lambda b, t: (t, 0))] * 3,
        out_specs=[pl.BlockSpec(_VREG, lambda b, t: (0, b))] * 2,
        out_shape=[jax.ShapeDtypeStruct((SUBLANES, nbb * LANES), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(keys2, vals2, valid2)
    return out[0, :num_buckets], cnt[0, :num_buckets]
