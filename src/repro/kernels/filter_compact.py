"""Stream compaction (filter) for TPU.

Hardware adaptation: the CUDA idiom is warp-ballot + shared-memory scatter.
TPUs have neither.  The jit'd wrapper first takes each 128-lane row's keep
count and its exclusive global offset ``off[r]`` (two reductions over
``n / 128`` values).  The kernel then does the whole O(n) pass, stitch
included:

* **Row pass.**  Each row of a ``(tile_rows, 128)`` tile is compacted on its
  own: the exclusive keep-prefix-sum comes from one MXU matmul against a
  strictly upper-triangular 0/1 matrix (exact: integer sums below 128), and
  kept lane ``j`` moves to lane ``(off[r] + j) mod 128`` with one select per
  source lane — pure data movement, so every value (NaN payloads, ±inf and
  −0.0 included) arrives bit for bit.  A rotated row holds at most two
  pieces: one of output row ``off[r] // 128``, and the wrapped one of the
  next.
* **Merge.**  A ``fori_loop`` over the tile's rows merges the pieces into
  whole output rows in a VMEM staging buffer, by select under lane masks
  (read-modify-write at a dynamic sublane).  The staged rows go to HBM by
  one DMA at the tile's first output row, rounded down to a multiple of 8.
  The grid runs in order: the 8-row group holding the tile's last, partly
  filled, output row is carried to the next tile, whose DMA waits for this
  one's because the two overlap there.

Slots from ``count`` on are set to ``fill`` by one fused select in the
wrapper.  Until this design the wrapper stitched the rows with an XLA
scatter into ``n + 1`` slots, which a TPU v5e ran as a sort of
(s32[n], f32[n]) and a serial scatter: at 2^22 elements the Pallas row
pass took 2.16 ms, the sort 3.79 ms and the scatter 20.4 ms.  The kernel
with the stitch inside takes 2.72 ms there, and the whole filter 2.78 ms
(one call each, device time, TPU v5e).
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

DEFAULT_TILE_ROWS = 64
ROW_BITS = LANES.bit_length() - 1  # off >> ROW_BITS is off // LANES


def _compact_kernel(off_ref, cnt_ref, x_ref, keep_ref, out_hbm,
                    rot_ref, stage_ref, carry_ref, sem):
    # off_ref, cnt_ref (1, TR) i32 in SMEM: each row's global offset and count
    # x_ref, keep_ref (TR, 128) f32; out_hbm (rows + TR + 8, 128) f32 in HBM
    # rot_ref (TR, 128) rotated rows; stage_ref (2, TR + 8, 128) staged
    # output rows, one slot per DMA in flight; carry_ref (8, 128)
    t, last = pl.program_id(0), pl.num_programs(0) - 1
    tr = x_ref.shape[0]
    slot = t % 2
    base = off_ref[0, 0] // (LANES * SUBLANES) * SUBLANES  # first output row

    keep = keep_ref[...]
    src = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    excl = jnp.dot(keep, (src < dst).astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    below = (jax.lax.broadcasted_iota(jnp.int32, (tr, tr), 1)
             < jax.lax.broadcasted_iota(jnp.int32, (tr, tr), 0))
    above = jnp.dot(below.astype(jnp.float32), keep,
                    preferred_element_type=jnp.float32)
    # off[r] mod 128 as a column: the tile's offset plus the rows above
    shift = (off_ref[0, 0] + jnp.sum(above, axis=1, keepdims=True)
             .astype(jnp.int32)) & (LANES - 1)
    # rotated slot of each kept lane within its row; -1 for dropped lanes
    slot_of = jnp.where(keep > 0.0, (excl.astype(jnp.int32) + shift) & (LANES - 1), -1)
    x = x_ref[...]
    shape = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    rot = jnp.zeros(shape, jnp.float32)
    for i in range(LANES):  # source lane i → its slot in every row at once
        hit = jnp.broadcast_to(slot_of[:, i:i + 1], shape) == lane
        rot = jnp.where(hit, jnp.broadcast_to(x[:, i:i + 1], shape), rot)
    rot_ref[...] = rot

    # lanes no kept value reaches keep what the buffers held: all of them
    # lie at or past the count, which the wrapper fills
    stage = stage_ref.at[slot]
    stage[0:SUBLANES, :] = carry_ref[...]
    row_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def merge(r, c):
        off, cnt = off_ref[0, r], cnt_ref[0, r]
        q, s = (off >> ROW_BITS) - base, off & (LANES - 1)
        piece = rot_ref[pl.ds(r, 1), :]
        head = (row_lane >= s) & (row_lane < s + cnt)
        stage[pl.ds(q, 1), :] = jnp.where(head, piece, stage[pl.ds(q, 1), :])
        wrap = row_lane < s + cnt - LANES
        stage[pl.ds(q + 1, 1), :] = jnp.where(wrap, piece, stage[pl.ds(q + 1, 1), :])
        return c

    jax.lax.fori_loop(0, tr, merge, None)
    end = off_ref[0, tr - 1] + cnt_ref[0, tr - 1]
    nxt = pl.multiple_of(end // (LANES * SUBLANES) * SUBLANES - base, SUBLANES)
    carry_ref[...] = stage[pl.ds(nxt, SUBLANES), :]

    def copy(k, at):
        return pltpu.make_async_copy(
            stage_ref.at[k], out_hbm.at[pl.ds(at, tr + SUBLANES)], sem.at[k])

    @pl.when(t > 0)
    def _():  # the previous tile's rows overlap this tile's first group
        copy(1 - slot, 0).wait()

    this = copy(slot, pl.multiple_of(base, SUBLANES))
    this.start()

    @pl.when(t == last)
    def _():
        this.wait()


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
    cnt = jnp.sum(keep2, axis=1).astype(jnp.int32)  # (rows,)
    off = jnp.cumsum(cnt) - cnt  # exclusive prefix
    total = jnp.sum(cnt)
    smem = pl.BlockSpec((pl.Squeezed(), 1, tr), lambda t: (t, 0, 0),
                        memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec((tr, LANES), lambda t: (t, 0))
    stitched = pl.pallas_call(
        _compact_kernel,
        grid=(rows // tr,),
        in_specs=[smem, smem, vmem, vmem],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((rows + tr + SUBLANES, LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tr, LANES), jnp.float32),
            pltpu.VMEM((2, tr + SUBLANES, LANES), jnp.float32),
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(off.reshape(-1, 1, tr), cnt.reshape(-1, 1, tr),
      to_lanes(x.astype(jnp.float32), rows, 0.0), keep2)
    kept = jnp.arange(n) < total
    return jnp.where(kept, stitched.reshape(-1)[:n], fill).astype(x.dtype), total
