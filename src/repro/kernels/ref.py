"""Pure-jnp oracles for every Pallas kernel (the correctness contracts).

Each function is the mathematically transparent reference the kernels are
validated against (interpret=True on CPU; Mosaic on real TPUs).  Several
are also the ``xla`` backend's paths in ``ops.py`` (CPU cannot lower Mosaic).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------------- #
# segment_reduce: groupby-aggregate partials                                   #
# --------------------------------------------------------------------------- #


def segment_reduce_ref(
    keys: jnp.ndarray,  # int32[n] in [0, num_buckets)
    values: jnp.ndarray,  # f32[n]
    valid: jnp.ndarray,  # bool[n]
    num_buckets: int,
    mode: str = "sum",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (reduced[num_buckets], counts[num_buckets])."""
    v = jnp.where(valid, values, _neutral(mode, values.dtype))
    counts = jax.ops.segment_sum(
        valid.astype(values.dtype), keys, num_segments=num_buckets
    )
    if mode == "sum":
        red = jax.ops.segment_sum(v, keys, num_segments=num_buckets)
    elif mode == "min":
        red = jax.ops.segment_min(v, keys, num_segments=num_buckets)
    elif mode == "max":
        red = jax.ops.segment_max(v, keys, num_segments=num_buckets)
    else:
        raise ValueError(mode)
    return red, counts


def _neutral(mode: str, dtype) -> jnp.ndarray:
    if mode == "sum":
        return jnp.asarray(0, dtype)
    if mode == "min":
        return jnp.asarray(jnp.inf, dtype)
    if mode == "max":
        return jnp.asarray(-jnp.inf, dtype)
    raise ValueError(mode)


# --------------------------------------------------------------------------- #
# masked_stats: fused single-pass describe                                     #
# --------------------------------------------------------------------------- #


def masked_stats_ref(x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """(count, sum, m2, min, max) over the valid entries — f32[5].

    m2 is the centered second moment Σ m·(x − mean)², computed two-pass here
    (the kernels accumulate it tile-wise with Chan's pairwise update)."""
    m = mask.astype(x.dtype)
    big = jnp.asarray(jnp.inf, x.dtype)
    n = jnp.sum(m)
    s = jnp.sum(x * m)
    mean = s / jnp.maximum(n, 1)
    d = (x - mean) * m
    return jnp.stack(
        [
            n,
            s,
            jnp.sum(d * d),
            jnp.min(jnp.where(mask, x, big)),
            jnp.max(jnp.where(mask, x, -big)),
        ]
    )


# --------------------------------------------------------------------------- #
# filter_compact: stream compaction                                            #
# --------------------------------------------------------------------------- #


def filter_compact_ref(
    x: jnp.ndarray, keep: jnp.ndarray, fill: float = 0.0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable compaction: kept values first (original order), padded with
    ``fill``.  Returns (compacted[n], count[])."""
    n = x.shape[0]
    pos = jnp.cumsum(keep) - 1
    out = jnp.full((n,), fill, x.dtype)
    out = out.at[jnp.where(keep, pos, n)].set(x, mode="drop")
    return out, jnp.sum(keep)


# --------------------------------------------------------------------------- #
# topk: head-after-sort partial selection                                      #
# --------------------------------------------------------------------------- #


def topk_ref(x: jnp.ndarray, k: int, largest: bool = True) -> jnp.ndarray:
    """Top-k values, sorted (descending if largest)."""
    s = jnp.sort(x)
    return s[-k:][::-1] if largest else s[:k]
