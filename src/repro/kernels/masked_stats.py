"""Fused single-pass masked statistics (the `describe` hot loop) for TPU.

One HBM read of the column produces count/sum/m2/min/max simultaneously —
the memory-bound fusion that replaces five separate passes.  The column
streams through the grid as ``(tile_rows, 128)`` blocks (see
:mod:`.lanes`); the running moments live in VMEM scratch, each statistic
broadcast over a whole (8, 128) register so every update is a full-vreg
operation; one final write.

``m2`` is the centered second moment Σ m·(x − mean)², accumulated with Chan's
pairwise update (per-tile moment about the tile's own mean + cross-mean
correction on merge) so Var = m2/n stays accurate in f32 when |mean| ≫ std —
a raw sum of squares cancels catastrophically in that regime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .lanes import LANES, SUBLANES, row_layout, tile_max, tile_min, tile_sum, to_lanes

DEFAULT_TILE_ROWS = 512  # 64K elements (256 KiB of f32) per grid step
_VREG = (SUBLANES, LANES)


def _stats_kernel(
    x_ref,  # (TR, 128) f32
    m_ref,  # (TR, 128) f32, 1.0 = valid
    out_ref,  # (8, 128) f32: row i holds statistic i of (count, sum, m2, min, max)
    cnt_scr, sum_scr, m2_scr, mn_scr, mx_scr,  # (8, 128) f32 running moments
    *,
    num_tiles: int,
):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        cnt_scr[...] = jnp.zeros(_VREG, jnp.float32)
        sum_scr[...] = jnp.zeros(_VREG, jnp.float32)
        m2_scr[...] = jnp.zeros(_VREG, jnp.float32)
        mn_scr[...] = jnp.full(_VREG, jnp.inf, jnp.float32)
        mx_scr[...] = jnp.full(_VREG, -jnp.inf, jnp.float32)

    x = x_ref[...]
    mf = m_ref[...]
    m = mf > 0.0
    cnt, s, m2 = cnt_scr[...], sum_scr[...], m2_scr[...]
    tcnt1 = tile_sum(mf)  # (1, 1)
    tsum1 = tile_sum(x * mf)
    tmean1 = tsum1 / jnp.maximum(tcnt1, 1.0)
    d = (x - tmean1) * mf
    tcnt = jnp.broadcast_to(tcnt1, _VREG)
    tsum = jnp.broadcast_to(tsum1, _VREG)
    tmean = jnp.broadcast_to(tmean1, _VREG)
    tm2 = jnp.broadcast_to(tile_sum(d * d), _VREG)
    n = cnt + tcnt
    delta = tmean - s / jnp.maximum(cnt, 1.0)
    merged_m2 = m2 + tm2 + delta * delta * cnt * tcnt / jnp.maximum(n, 1.0)
    # all-masked tiles (padding) are exact no-ops for the moment slots
    live = tcnt > 0.0
    cnt_scr[...] = jnp.where(live, n, cnt)
    sum_scr[...] = jnp.where(live, s + tsum, s)
    m2_scr[...] = jnp.where(live, merged_m2, m2)
    mn_scr[...] = jnp.minimum(
        mn_scr[...], jnp.broadcast_to(tile_min(jnp.where(m, x, jnp.inf)), _VREG)
    )
    mx_scr[...] = jnp.maximum(
        mx_scr[...], jnp.broadcast_to(tile_max(jnp.where(m, x, -jnp.inf)), _VREG)
    )

    @pl.when(t == num_tiles - 1)
    def _fin():
        row = jax.lax.broadcasted_iota(jnp.int32, _VREG, 0)
        out = jnp.zeros(_VREG, jnp.float32)
        for i, scr in enumerate((cnt_scr, sum_scr, m2_scr, mn_scr, mx_scr)):
            out = jnp.where(row == i, scr[...], out)
        out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
@obs.device_scope("masked_stats")
def masked_stats(
    x: jnp.ndarray,  # f32[n]
    mask: jnp.ndarray,  # bool[n]
    tile_rows: int = DEFAULT_TILE_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns f32[5]: (count, sum, m2, min, max) over valid entries."""
    rows, tr = row_layout(x.shape[0], tile_rows)
    nt = rows // tr
    xs = to_lanes(x.astype(jnp.float32), rows, 0.0)
    ms = to_lanes(mask.astype(jnp.float32), rows, 0.0)
    out = pl.pallas_call(
        functools.partial(_stats_kernel, num_tiles=nt),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((tr, LANES), lambda t: (t, 0)),
            pl.BlockSpec((tr, LANES), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec(_VREG, lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(_VREG, jnp.float32),
        scratch_shapes=[pltpu.VMEM(_VREG, jnp.float32) for _ in range(5)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xs, ms)
    return out[:5, 0]
