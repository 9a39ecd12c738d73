"""Top-k selection (head-after-sort pushdown, paper §5.1) for TPU.

The paper notes sort-interactions should "prioritize the generation of the K
first sorted results".  Per ``(tile_rows, 128)`` VMEM tile we run k rounds
of (max, mask-first-occurrence) on the VPU — no data-dependent control flow,
no sort network bookkeeping — writing round i's winner to lane i of the
tile's (8, 128) output block; the wrapper merges tile winners
(k·num_tiles values) with one final jnp sort (tiny).  k ≤ 128: one lane per
winner.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .lanes import LANES, SUBLANES, row_layout, tile_max, tile_min, to_lanes

DEFAULT_TILE_ROWS = 512  # 64K candidates per grid step
_VREG = (SUBLANES, LANES)
_NEG = -jnp.inf


def _topk_kernel(x_ref, out_ref, vals_scr, *, k: int):
    # x_ref (TR, 128) f32; out_ref (8, 128): lane i = round i's winner
    shape = x_ref.shape
    vals_scr[...] = x_ref[...]
    # flat position (exact in f32 below 2^24) picks the first occurrence
    flat = (
        jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
        + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, _VREG, 1)

    def round_fn(i, best):
        vals = vals_scr[...]
        cur = tile_max(vals)  # (1, 1)
        first = tile_min(jnp.where(vals == cur, flat, jnp.inf))
        vals_scr[...] = jnp.where(flat == first, _NEG, vals)
        return jnp.where(lane == i, cur, best)

    out_ref[...] = jax.lax.fori_loop(
        0, k, round_fn, jnp.full(_VREG, _NEG, jnp.float32)
    )


@functools.partial(
    jax.jit, static_argnames=("k", "largest", "tile_rows", "interpret")
)
@obs.device_scope("topk")
def topk(
    x: jnp.ndarray,  # f32[n]
    k: int,
    largest: bool = True,
    tile_rows: int = DEFAULT_TILE_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Top-k values of x, sorted descending (ascending if largest=False)."""
    if not 1 <= k <= LANES:
        raise ValueError(f"topk: k={k} outside [1, {LANES}]")
    xs = x.astype(jnp.float32)
    xs = xs if largest else -xs
    rows, tr = row_layout(xs.shape[0], tile_rows)
    nt = rows // tr
    winners = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(nt,),
        in_specs=[pl.BlockSpec((tr, LANES), lambda t: (t, 0))],
        out_specs=pl.BlockSpec(_VREG, lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((nt * SUBLANES, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tr, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(to_lanes(xs, rows, _NEG))
    cand = winners.reshape(nt, SUBLANES, LANES)[:, 0, :k].reshape(-1)
    merged = jnp.sort(cand)[::-1][:k]
    out = merged if largest else -merged
    return out.astype(x.dtype)
