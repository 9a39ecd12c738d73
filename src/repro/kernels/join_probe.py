"""Band-merge join probe (broadcast dim-table join, paper §5.1) for TPU.

For each left key against the ascending right keys ``r``:

    ``pos[i] = #{ j : r[j] < l[i] }``   (== searchsorted-left)
    ``hit[i] = any(r[j] == l[i])``

Hardware adaptation: a hash-table probe or a binary search is a random
gather per key, the access pattern TPUs are worst at; comparing every left
key with every right key costs O(n·m).  So the probe merges:

* **Bands (host).**  ``band_order`` groups the left keys into 256 value
  bands of equal width over the right side's range of keys, one stable
  radix pass over a byte per key, and the caller uploads them in that
  order.  ``merge_probe`` cuts them into tiles of 1,024 keys (one ``(8,
  128)`` vector register each); a tile spans about one band's range of
  keys, or less where a band holds many tiles' worth.
  The device sorts nothing: an XLA sort of 2^15 keys or more takes the TPU
  compiler tens of seconds per length, in every process that meets it.
* **Directory.**  The right keys are cut into blocks of ``right_block``.  A
  block lies wholly below a tile when its last key is below the tile's min,
  and wholly above it when its first key is above the tile's max.  Counting
  the tiles' bounds against the blocks' first and last keys (XLA, ``tiles ×
  blocks`` compares) gives each tile the blocks ``[lo, hi)`` it overlaps.
* **Merge.**  The Pallas kernel walks a grid of tile groups; for each tile it
  DMAs the blocks ``lo .. hi - 1`` from HBM into SMEM, two in flight, and
  compares every right key there, as a broadcast scalar, with the tile's
  register of left keys.  ``pos`` is ``min(lo · right_block, m)`` (the keys
  of the blocks below) plus the count; ``hit`` is any equality.

The answers hold for keys in any order: the order only sets how many right
keys a tile sweeps, its range of keys plus at most two blocks it does not
need, so the compares come to about ``max(m · 1024, n · m / 256) + n · 2 ·
right_block``, not ``n · m``.  The outputs stay in band order; the host,
which gathers the right columns anyway, puts them back in row order.

One call, TPU v5e, left keys uniform over a dense right side: 2^17 left
keys against 1.5M right keys take 7.5 ms of device time (the host's order
2.1 ms); 2^23 against 200k 41 ms (the host's order 65 ms, in four
threads); 2^16 against 65,536 0.38 ms.  A sparse tile, whose keys span the
whole right side, sweeps all of it: 1.5M keys take about 4.5 ms.

Pads are ``NaN`` on both sides.  A NaN key compares false with everything:
it never counts and never matches, and a NaN left key gets ``pos = 0``;
the tile bounds skip NaN keys.  ``±inf`` real keys compare exactly, and
integer keys are exact in f32 below 2^24.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .lanes import LANES, SUBLANES, row_layout, to_lanes

TILE_KEYS = SUBLANES * LANES  # left keys per merge tile: one vector register
DEFAULT_TILES_PER_STEP = 8  # merge tiles per grid step
DEFAULT_RIGHT_BLOCK = 512  # right keys per SMEM block
BANDS = 256  # value bands of the host's ordering: one radix pass over a byte
ORDER_CHUNK = 1 << 20  # left keys per thread of the host's ordering
ORDER_THREADS = 4
_UNROLL = 8  # right keys per loop step (right blocks are multiples of 128)


def _merge_kernel(lo_ref, hi_ref, l_ref, r_hbm, pos_ref, hit_ref, rbuf, sem, *,
                  m: int):
    # lo_ref, hi_ref (1, S) i32 in SMEM: each tile's first and end block
    # l_ref (S * 8, 128) f32 left keys in band order; r_hbm (blocks, 1, RB) f32 in HBM
    # pos_ref, hit_ref (S * 8, 128) i32; rbuf (2, 1, RB) f32 SMEM; sem DMA (2,)
    right_block = rbuf.shape[2]

    def fetch(b, slot):
        return pltpu.make_async_copy(r_hbm.at[b], rbuf.at[slot], sem.at[slot])

    for s in range(lo_ref.shape[1]):
        rows = pl.ds(s * SUBLANES, SUBLANES)
        lo, hi = lo_ref[0, s], hi_ref[0, s]
        lk = l_ref[rows, :]

        @pl.when(lo < hi)
        def _():
            fetch(lo, 0).start()

        def block(b, carry):
            slot = (b - lo) % 2

            @pl.when(b + 1 < hi)
            def _():
                fetch(b + 1, 1 - slot).start()

            fetch(b, slot).wait()

            def keys(j, c):
                cnt, hit = c
                for u in range(_UNROLL):  # manual unroll: amortise loop overhead
                    r = rbuf[slot, 0, j * _UNROLL + u]
                    cnt = jnp.where(r < lk, cnt + 1, cnt)
                    hit = jnp.where(r == lk, 1, hit)
                return cnt, hit

            return jax.lax.fori_loop(0, right_block // _UNROLL, keys, carry)

        zero = jnp.zeros(lk.shape, jnp.int32)
        cnt, hit = jax.lax.fori_loop(lo, hi, block, (zero, zero))
        # the blocks below the tile hold keys below all of its keys; a NaN
        # key counts nothing
        below = jnp.minimum(lo * right_block, m)
        pos_ref[rows, :] = jnp.where(lk == lk, cnt + below, 0)
        hit_ref[rows, :] = hit


def band_range(r_sorted) -> Tuple[float, float]:
    """The least and the greatest finite key of the ascending right side,
    ``(0, 0)`` without any: the range ``band_order`` cuts into bands."""
    r = np.asarray(r_sorted)
    a = int(np.searchsorted(r, -np.inf, side="right"))
    b = int(np.searchsorted(r, np.inf, side="left")) - 1
    return (float(r[a]), float(r[b])) if a <= b else (0.0, 0.0)


def _order_into(keys, lo, hi, perm, out, start) -> None:
    k = np.asarray(keys, np.float32)
    with np.errstate(over="ignore", invalid="ignore"):  # huge and ±inf keys
        band = k - np.float32(lo)
        band *= np.float32((BANDS - 1) / (hi - lo) if hi > lo else 0.0)
    np.fmax(band, 0, out=band)  # below the range, -inf and NaN: the first band
    np.fmin(band, BANDS - 1, out=band)
    order = np.argsort(band.astype(np.uint8), kind="stable")
    np.take(k, order, out=out)
    np.add(order, start, out=perm)


@functools.lru_cache(maxsize=None)
def _order_pool() -> ThreadPoolExecutor:
    return ThreadPoolExecutor(ORDER_THREADS, thread_name_prefix="band_order")


def order_cuts(n: int) -> np.ndarray:
    """The row ranges ``[cuts[i], cuts[i + 1])`` that ``band_order`` orders
    each on its own: one below ``2 · ORDER_CHUNK`` rows, else up to
    ``ORDER_THREADS`` of at least ``ORDER_CHUNK`` rows each."""
    chunks = max(1, min(ORDER_THREADS, n // ORDER_CHUNK))
    return np.linspace(0, n, chunks + 1).astype(np.intp)


def over_chunks(fn, cuts) -> None:
    """``fn(a, b)`` for each range of ``cuts``: one range on the calling
    thread, more on the ordering's pool, returning when all are done and
    raising what a range raised.  ``fn`` opens no spans, so the caller's
    span holds the wait."""
    if len(cuts) == 2:
        fn(int(cuts[0]), int(cuts[1]))
        return
    list(_order_pool().map(fn, cuts[:-1], cuts[1:]))


def band_order(keys, lo: float, hi: float) -> Tuple[np.ndarray, np.ndarray]:
    """``(perm, keys[perm] as f32)``, on the host: the order in which to hand
    the left keys to ``merge_probe``.  The keys fall in ``BANDS`` value bands
    of equal width over ``[lo, hi]`` (the right side's ``band_range``),
    ascending, and keep their row order within a band; keys below the range,
    ``-inf`` and NaN fall in the first band, keys above it and ``+inf`` in
    the last.  One stable argsort of a byte per key: a single radix pass in
    numpy.  Each range of ``order_cuts`` is ordered on its own, up to
    ``ORDER_THREADS`` at once, one after another: a band of a chunk still
    holds over a thousand keys, so the tiles stay as narrow, and the entries
    of ``perm`` in a range are that range's rows."""
    k = np.asarray(keys)
    n = k.shape[0]
    perm, out = np.empty(n, np.intp), np.empty(n, np.float32)
    over_chunks(lambda a, b: _order_into(k[a:b], lo, hi, perm[a:b], out[a:b], a),
                order_cuts(n))
    return perm, out


@functools.partial(
    jax.jit, static_argnames=("tiles_per_step", "right_block", "interpret")
)
@obs.device_scope("join_probe")
def merge_probe(
    keys: jnp.ndarray,  # f32[L] in band order (``band_order``), NaN for pads
    r_sorted: jnp.ndarray,  # f32[m] ascending, unique among finite entries
    tiles_per_step: int = DEFAULT_TILES_PER_STEP,
    right_block: int = DEFAULT_RIGHT_BLOCK,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(pos int32[L'], hit bool[L'])``, ``L' >= L`` padded to whole grid
    steps: each key's searchsorted-left position in ``r_sorted`` and whether
    it is there.  Right for keys in any order; fast where each tile of 1,024
    spans a narrow range of keys."""
    m = r_sorted.shape[0]
    if right_block % LANES:
        raise ValueError(f"right_block={right_block} is not a multiple of 128")
    rows, tr = row_layout(keys.shape[0], tiles_per_step * SUBLANES)
    keys = to_lanes(keys.astype(jnp.float32), rows, jnp.nan).reshape(-1)
    tiles = keys.reshape(-1, TILE_KEYS)
    finite = ~jnp.isnan(tiles)
    tmin = jnp.min(jnp.where(finite, tiles, jnp.inf), axis=1)
    tmax = jnp.max(jnp.where(finite, tiles, -jnp.inf), axis=1)

    rb = min(right_block, -(-m // LANES) * LANES)
    nblocks = -(-m // rb)
    r = r_sorted.astype(jnp.float32)
    ends = jnp.minimum(jnp.arange(1, nblocks + 1) * rb, m) - 1
    first, last = r[jnp.arange(nblocks) * rb], r[ends]
    lo = jnp.sum(last[None, :] < tmin[:, None], axis=1, dtype=jnp.int32)
    hi = jnp.sum(first[None, :] <= tmax[:, None], axis=1, dtype=jnp.int32)
    blocks = jnp.pad(r, (0, nblocks * rb - m), constant_values=jnp.nan)

    steps = rows // tr
    per_step = tr // SUBLANES
    smem = pl.BlockSpec((pl.Squeezed(), 1, per_step), lambda t: (t, 0, 0),
                        memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec((tr, LANES), lambda t: (t, 0))
    pos, hit = pl.pallas_call(
        functools.partial(_merge_kernel, m=m),
        grid=(steps,),
        in_specs=[smem, smem, vmem, pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=[vmem, vmem],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.int32)] * 2,
        scratch_shapes=[
            pltpu.SMEM((2, 1, rb), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(lo.reshape(steps, 1, per_step), hi.reshape(steps, 1, per_step),
      keys.reshape(rows, LANES), blocks.reshape(nblocks, 1, rb))
    return pos.reshape(-1), hit.reshape(-1) > 0


def join_probe(
    l_keys,  # f32[n]
    r_sorted: jnp.ndarray,  # f32[m] ascending, unique among finite entries
    tiles_per_step: int = DEFAULT_TILES_PER_STEP,
    right_block: int = DEFAULT_RIGHT_BLOCK,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, np.ndarray]:
    """Returns ``(pos int32[n], hit bool[n], perm)`` in band order: entry
    ``i`` belongs to the left key ``l_keys[perm[i]]``, ``pos[i]`` is its
    searchsorted-left position in ``r_sorted`` and ``hit[i]`` whether
    ``r_sorted`` holds it."""
    perm, keys = band_order(np.asarray(l_keys), *band_range(np.asarray(r_sorted)))
    pos, hit = merge_probe(jnp.asarray(keys), r_sorted,
                           tiles_per_step=tiles_per_step,
                           right_block=right_block, interpret=interpret)
    return pos[:len(perm)], hit[:len(perm)], perm
