"""Sorted-lookup join probe (broadcast dim-table join, paper §5.1) for TPU.

Hardware adaptation: a hash-table probe is a random gather — the access
pattern TPUs are worst at.  With the (small, broadcast) right side sorted,
the probe becomes *counting*: for each left key,

    ``pos[i] = #{ j : r_sorted[j] < l_keys[i] }``   (== searchsorted-left)
    ``hit[i] = any(r_sorted[j] == l_keys[i])``

Left keys stream through the grid as ``(tile_rows, 128)`` VMEM tiles; the
right side streams as blocks of scalars in SMEM.  Each right key is one
scalar-broadcast compare against a register-resident group of left rows,
accumulated across right blocks.  No data-dependent control flow, no
gather: the host gathers right columns once with the resulting positions.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .lanes import LANES, SUBLANES, row_layout, to_lanes

DEFAULT_TILE_ROWS = 256  # 32K left keys per grid step
DEFAULT_RIGHT_BLOCK = 2048  # right keys per SMEM block
_GROUP_ROWS = 4 * SUBLANES  # left rows held in registers per right sweep
_UNROLL = 8  # right keys per loop step (right blocks are multiples of 128)


def _probe_kernel(
    l_ref,  # (TR, 128) f32 left keys (NaN padded)
    r_ref,  # (RB,) f32 sorted right keys in SMEM (NaN padded)
    pos_ref,  # (TR, 128) i32 running counts
    hit_ref,  # (TR, 128) i32 running any-equal (0/1)
    *,
    right_block: int,
    group_rows: int,
):
    rb = pl.program_id(1)

    @pl.when(rb == 0)
    def _init():
        pos_ref[...] = jnp.zeros_like(pos_ref)
        hit_ref[...] = jnp.zeros_like(hit_ref)

    def group(g, _):
        rows = pl.ds(pl.multiple_of(g * group_rows, group_rows), group_rows)
        lk = l_ref[rows, :]

        def right_keys(j, carry):
            pos, hit = carry
            for u in range(_UNROLL):  # manual unroll: amortise loop overhead
                r = r_ref[j * _UNROLL + u]
                # int32 accumulation: f32 counts would round away increments
                # past 2^24 rows of right side, corrupting the gather positions
                pos = pos + jnp.where(r < lk, 1, 0)
                hit = jnp.maximum(hit, jnp.where(r == lk, 1, 0))
            return pos, hit

        pos, hit = jax.lax.fori_loop(
            0, right_block // _UNROLL, right_keys,
            (pos_ref[rows, :], hit_ref[rows, :]),
        )
        pos_ref[rows, :] = pos
        hit_ref[rows, :] = hit
        return 0

    jax.lax.fori_loop(0, l_ref.shape[0] // group_rows, group, 0)


@functools.partial(
    jax.jit, static_argnames=("tile_rows", "right_block", "interpret")
)
@obs.device_scope("join_probe")
def join_probe(
    l_keys: jnp.ndarray,  # f32[n]
    r_sorted: jnp.ndarray,  # f32[m] ascending, unique among finite entries
    tile_rows: int = DEFAULT_TILE_ROWS,
    right_block: int = DEFAULT_RIGHT_BLOCK,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns ``(pos int32[n], hit bool[n])`` — searchsorted-left positions
    of each left key in ``r_sorted`` and whether an exact match exists.

    Pads are ``NaN`` on both sides: every comparison against NaN is false, so
    pad entries never count toward ``pos`` and never match — which also means
    the counting formulation (unlike a binary search) needs no care about
    where pads land relative to real keys, and ``±inf`` *real* keys compare
    exactly."""
    n = l_keys.shape[0]
    m = r_sorted.shape[0]
    rows, tr = row_layout(n, tile_rows)
    group_rows = _GROUP_ROWS if tr % _GROUP_ROWS == 0 else SUBLANES
    rb = min(right_block, -(-m // LANES) * LANES)
    mp = -(-m // rb) * rb
    r = r_sorted.astype(jnp.float32)
    if mp != m:
        r = jnp.pad(r, (0, mp - m), constant_values=jnp.nan)
    pos, hit = pl.pallas_call(
        functools.partial(_probe_kernel, right_block=rb, group_rows=group_rows),
        grid=(rows // tr, mp // rb),
        in_specs=[
            pl.BlockSpec((tr, LANES), lambda t, b: (t, 0)),
            pl.BlockSpec((rb,), lambda t, b: (b,), memory_space=pltpu.SMEM),
        ],
        out_specs=[pl.BlockSpec((tr, LANES), lambda t, b: (t, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(to_lanes(l_keys.astype(jnp.float32), rows, jnp.nan), r)
    return pos.reshape(-1)[:n], hit.reshape(-1)[:n] > 0
