"""Jit'd dispatch wrappers for the Pallas kernels.

Backend selection:
  * ``"pallas"``    — Mosaic lowering (real TPU),
  * ``"interpret"`` — Pallas interpret mode (CPU correctness; used by tests),
  * ``"xla"``       — the pure-jnp reference math (CPU / fallback; same
                       semantics, XLA-fused).

Default: pallas on TPU backends, xla elsewhere — so library code can call
these unconditionally and stay runnable on this CPU container while targeting
TPU.
"""
from __future__ import annotations

import functools
import threading
from contextlib import contextmanager
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import ref
from .filter_compact import filter_compact as _filter_pallas
from .join_probe import merge_probe
from .masked_stats import masked_stats as _stats_pallas
from .segment_reduce import segment_reduce as _segment_pallas
from .topk import topk as _topk_pallas

_FORCED: Optional[str] = None
_TLS = threading.local()  # per-thread override (scoped, race-free)


def set_backend(backend: Optional[str]) -> None:
    """Force a backend globally ("pallas" | "interpret" | "xla" | None=auto)."""
    global _FORCED
    _FORCED = backend


@contextmanager
def local_backend(backend: Optional[str]):
    """Thread-local scoped backend override.  Takes precedence over
    :func:`set_backend`'s process-global.  Use this from code that may run on
    multiple threads at once (the frame layer's background worker executes
    units concurrently with foreground interactions): a process-global
    save/restore would race and could strand the global in the wrong state."""
    prev = getattr(_TLS, "forced", None)
    _TLS.forced = backend
    try:
        yield
    finally:
        _TLS.forced = prev


def backend() -> str:
    local = getattr(_TLS, "forced", None)
    if local is not None:
        return local
    if _FORCED is not None:
        return _FORCED
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def segment_reduce(keys, values, valid, num_buckets: int, mode: str = "sum"):
    b = backend()
    if b == "xla":
        return ref.segment_reduce_ref(keys, values, valid, num_buckets, mode)
    return _segment_pallas(
        keys, values, valid, num_buckets, mode=mode, interpret=(b == "interpret")
    )


def masked_stats(x, mask):
    b = backend()
    if b == "xla":
        return ref.masked_stats_ref(x, mask)
    return _stats_pallas(x, mask, interpret=(b == "interpret"))


def filter_compact(x, keep, fill: float = 0.0):
    b = backend()
    if b == "xla":
        return ref.filter_compact_ref(x, keep, fill)
    return _filter_pallas(x, keep, fill=fill, interpret=(b == "interpret"))


def topk(x, k: int, largest: bool = True):
    b = backend()
    if b == "xla":
        return ref.topk_ref(x, k, largest)
    return _topk_pallas(x, k, largest=largest, interpret=(b == "interpret"))


# --------------------------------------------------------------------------- #
# Padded / batched entry points for the frame layer                            #
#                                                                              #
# The dispatchers above jit-specialise on exact array shapes, so calling them  #
# once per dataframe partition (whose row counts all differ slightly) would    #
# recompile per partition — the 20× eager-recompile problem noted in           #
# `repro.frame.table`.  These wrappers round row counts up to power-of-two     #
# buckets (null-masked padding, semantics unchanged) so an entire table's      #
# partitions share a handful of compiled executables, and batch the per-column #
# describe pass into one call.                                                 #
# --------------------------------------------------------------------------- #

PAD_MIN = 512  # smallest padded length (also amortises tiny partitions)
_TILE = 16384  # scan-tile rows for the CPU/XLA paths: temps stay cache-resident


def upload(host, dtype=None) -> jnp.ndarray:
    """A host array on the device: the dtype conversion as a
    ``dispatch.prep`` span, the transfer as a ``dispatch.upload`` span with
    its bytes."""
    host = np.asarray(host)
    if dtype is not None and host.dtype != dtype:
        with obs.span("dispatch.prep"):
            host = host.astype(dtype)
    with obs.span("dispatch.upload", bytes=host.nbytes):
        return jnp.asarray(host)


def _device(x, dtype) -> jnp.ndarray:
    """``jnp.asarray(x, dtype)``, through :func:`upload` for a host array."""
    if isinstance(x, np.ndarray):
        return upload(x, dtype)
    return jnp.asarray(x, dtype)


def pad_len(n: int, minimum: int = PAD_MIN) -> int:
    """Next power-of-two bucket ≥ n (≥ minimum) — the shared jit shape."""
    if n <= minimum:
        return minimum
    return 1 << (int(n) - 1).bit_length()


def _pad1(x: jnp.ndarray, nb: int, value) -> jnp.ndarray:
    n = x.shape[0]
    if nb == n:
        return x
    return jnp.pad(x, (0, nb - n), constant_values=value)


def _stats_row_tiled(x: jnp.ndarray, m: jnp.ndarray, tile: int) -> jnp.ndarray:
    """One column's (count, sum, m2, min, max) via a lax.scan over tiles —
    the XLA mirror of the Pallas kernel's grid: one HBM pass, accumulators and
    per-tile temporaries stay in cache instead of materialising n-sized
    intermediates (≫ faster than the naive five-reduction form on CPU).

    ``m2`` is the centered second moment Σ m·(x − mean)², carried with Chan's
    pairwise update: each tile computes its moment about its *own* mean, then
    merges into the running accumulator with the cross-mean correction term.
    A raw sum of squares cancels catastrophically in f32 when |mean| ≫ std
    (ss and s²/n agree in their leading digits), which is exactly the regime
    where confidence intervals on shifted data go wrong."""
    nt = x.shape[0] // tile
    xt = x.reshape(nt, tile)
    mt = m.reshape(nt, tile)

    def body(acc, inp):
        xi, mi = inp
        mf = mi.astype(jnp.float32)
        cnt, s, m2, mn, mx = acc
        tcnt = mf.sum()
        tsum = (xi * mf).sum()
        tmean = tsum / jnp.maximum(tcnt, 1.0)
        d = (xi - tmean) * mf
        tm2 = (d * d).sum()
        n = cnt + tcnt
        delta = tmean - s / jnp.maximum(cnt, 1.0)
        merged_m2 = m2 + tm2 + delta * delta * cnt * tcnt / jnp.maximum(n, 1.0)
        # All-masked tiles (bucket padding) must stay exact no-ops so results
        # are invariant to how far the input was padded; gate on tcnt > 0.
        live = tcnt > 0
        return (
            jnp.where(live, n, cnt),
            jnp.where(live, s + tsum, s),
            jnp.where(live, merged_m2, m2),
            jnp.minimum(mn, jnp.where(mi, xi, jnp.inf).min()),
            jnp.maximum(mx, jnp.where(mi, xi, -jnp.inf).max()),
        ), None

    init = (
        jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0),
        jnp.float32(jnp.inf), jnp.float32(-jnp.inf),
    )
    acc, _ = jax.lax.scan(body, init, (xt, mt))
    return jnp.stack(acc)


@functools.partial(jax.jit, static_argnames=("tile",))
@obs.device_scope("masked_stats")
def _masked_stats_batch_xla(xs: jnp.ndarray, ms: jnp.ndarray, tile: int) -> jnp.ndarray:
    return jnp.stack(
        [_stats_row_tiled(xs[i], ms[i], tile) for i in range(xs.shape[0])]
    )


def masked_stats_batch(xs, ms) -> jnp.ndarray:
    """Batched fused describe pass: (C, n) values + (C, n) validity → (C, 5)
    rows of (count, sum, m2, min, max) where m2 = Σ m·(x − mean)² is the
    Chan-merged centered second moment.  One dispatch covers every numeric
    column of a partition; rows are padded to a shared shape bucket."""
    xs = jnp.asarray(xs, jnp.float32)
    ms = jnp.asarray(ms, bool)
    c, n = xs.shape
    nb = pad_len(n)
    if nb != n:
        xs = jnp.pad(xs, ((0, 0), (0, nb - n)))
        ms = jnp.pad(ms, ((0, 0), (0, nb - n)), constant_values=False)
    b = backend()
    if b == "xla":
        # Fixed-_TILE tiles regardless of bucket: every scan step reduces
        # exactly _TILE elements, so the result is invariant to how far the
        # input was padded (extra all-masked tiles are exact-neutral:
        # +0.0 for sums, ±inf for min/max).  The fused filter→stats
        # composites rely on this for bit-for-bit parity with the unfused
        # sequence — their reduce runs at the *parent* partition's bucket
        # while the unfused stats stage runs at the filtered bucket.
        if nb < _TILE:
            xs = jnp.pad(xs, ((0, 0), (0, _TILE - nb)))
            ms = jnp.pad(ms, ((0, 0), (0, _TILE - nb)), constant_values=False)
            nb = _TILE
        return _masked_stats_batch_xla(xs, ms, _TILE)
    interp = b == "interpret"
    return jnp.stack([_stats_pallas(xs[i], ms[i], interpret=interp) for i in range(c)])


def _topk_body(x: jnp.ndarray, k: int, largest: bool) -> jnp.ndarray:
    vals, _ = jax.lax.top_k(x if largest else -x, k)
    return vals if largest else -vals


_topk_xla = functools.partial(jax.jit, static_argnames=("k", "largest"))(
    obs.device_scope("topk")(_topk_body)
)


def topk_padded(x, k: int, largest: bool = True) -> jnp.ndarray:
    """`topk` on a shape-bucketed input (pads with the losing sentinel).

    The xla path uses ``lax.top_k`` directly (a single O(n) selection pass —
    far cheaper than the sort-based reference oracle)."""
    x = _device(x, jnp.float32)
    nb = pad_len(x.shape[0])
    sentinel = -jnp.inf if largest else jnp.inf
    xp = _pad1(x, nb, sentinel)
    if backend() == "xla":
        return _topk_xla(xp, k, largest)
    return topk(xp, k, largest=largest)


def filter_compact_padded(x, keep, fill: float = 0.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`filter_compact` on a shape-bucketed input; returns (compacted[n], count)."""
    x = jnp.asarray(x, jnp.float32)
    keep = jnp.asarray(keep, bool)
    n = x.shape[0]
    nb = pad_len(n)
    out, cnt = filter_compact(_pad1(x, nb, fill), _pad1(keep, nb, False), fill=fill)
    return out[:n], cnt


# -- full sort: exact f64 ordering on the f32 datapath ------------------------
#
# TPUs sort f32; dataframe sort keys are f64 (or int64 cast through f64 by the
# numpy reference).  Rounding keys to f32 would merge distinct keys into ties
# and silently reorder rows relative to the reference.  Instead each f64 key is
# split into THREE non-overlapping f32 components (Veltkamp-style residual
# splitting):
#
#     hi  = RN32(x),  mid = RN32(x - hi),  lo = RN32(x - hi - mid)
#
# When every component stays in f32's *normal* range, each residual spans
# ≤ 29 significant bits, both subtractions are exact in f64, and
# ``x == hi + mid + lo`` exactly (3 × 24 bits ≥ the 53-bit f64 mantissa).
# Because round-to-nearest is monotone, comparing ``(hi, mid, lo)``
# lexicographically is then equivalent to comparing ``x`` — so a stable
# multi-key ``lax.sort`` over the three components reproduces numpy's stable
# f64 argsort bit-for-bit.
#
# Exactness envelope: |x| = 0, or roughly 2^-100 < |x| < f32 max (≈ 2^128).
# Above the top the ``hi`` component overflows to ±inf; near and below the
# bottom the residuals land on (or under) f32's subnormal grid and lose bits,
# so distinct tiny keys collapse to identical component triples and sort as
# ties.  Callers must NOT rely on the magnitude bound alone: the backend gate
# (``_sort_keys_exact``) re-splits the keys and verifies the f64 identity
# ``hi + mid + lo == x`` for every key, falling back to numpy otherwise —
# exact reconstruction plus monotone rounding at each stage is sufficient for
# order equivalence (equal triples would reconstruct to one value, hence one
# key).  Unmasked NaNs are also gated out: they have no total order to
# preserve.


def split_f64(keys) -> Tuple:
    """Host-side exact 3-way f32 split of f64 sort keys.

    Non-finite keys (the ±inf null sentinels) keep ``hi`` and zero the
    residual components — ``inf - inf`` is NaN and would poison the
    lexicographic comparison."""
    keys = np.asarray(keys, np.float64)
    finite = np.isfinite(keys)
    hi = keys.astype(np.float32)
    r1 = np.zeros_like(keys)
    np.subtract(keys, hi.astype(np.float64), out=r1, where=finite)
    mid = r1.astype(np.float32)
    lo = (r1 - mid.astype(np.float64)).astype(np.float32)
    return hi, mid, lo


def _sort_order_body(hi: jnp.ndarray, mid: jnp.ndarray, lo: jnp.ndarray):
    iota = jnp.arange(hi.shape[0], dtype=jnp.int32)
    _, _, _, order = jax.lax.sort(
        (hi, mid, lo, iota), num_keys=3, is_stable=True
    )
    return order


_sort_order_xla = jax.jit(obs.device_scope("sort")(_sort_order_body))


def sort_order_padded(hi, mid, lo) -> jnp.ndarray:
    """Ascending stable argsort of exactly-split f64 keys; returns int32
    positions.  Rows pad to a shared shape bucket with ``(+inf, 0, 0)`` —
    lexicographically after every real row (stability keeps real ``+inf``
    null-sentinel rows, whose residuals are also zero, ahead of pads).

    All kernel backends share the jit'd ``lax.sort``: XLA's sort *is* the
    TPU-optimal implementation (the same bitonic network a hand-written
    Mosaic kernel would emit), so unlike the reduction kernels there is no
    separate Pallas path to dispatch to."""
    hi = _device(hi, jnp.float32)
    n = hi.shape[0]
    nb = pad_len(n)
    hi = _pad1(hi, nb, jnp.inf)
    mid = _pad1(_device(mid, jnp.float32), nb, 0.0)
    lo = _pad1(_device(lo, jnp.float32), nb, 0.0)
    return _sort_order_xla(hi, mid, lo)[:n]


def argsort_f64(keys) -> jnp.ndarray:
    """Stable ascending argsort of f64 keys, bit-for-bit equal to
    ``np.argsort(keys, kind="stable")``.  Precondition (see the envelope note
    above): no NaN, and every key must survive the 3×f32 split exactly —
    callers gate with ``_sort_keys_exact``, which rejects overflow (|x| ≥ f32
    max) and underflow (|x| ≲ 2^-100) magnitudes."""
    with obs.span("dispatch.prep"):
        parts = split_f64(keys)
    return sort_order_padded(*parts)


# -- sorted-lookup join probe -------------------------------------------------


@functools.partial(jax.jit, static_argnames=("m",))
@obs.device_scope("join_probe")
def _join_probe_xla(r_sorted: jnp.ndarray, l_keys: jnp.ndarray, m: int):
    pos = jnp.searchsorted(r_sorted, l_keys, side="left")
    hit = r_sorted[jnp.minimum(pos, m - 1)] == l_keys
    return pos, hit


def join_probe_padded(r_sorted, l_keys) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Probe each left key against the (ascending, unique) sorted right key
    array: returns ``(pos, hit)``, ``pos`` the searchsorted-left position in
    ``[0, m]`` and ``hit`` marking exact matches, in the order of
    ``l_keys``.  The kernel backends take the keys in any order and are fast
    in ``join_probe.band_order``'s.  Left keys pad to a shape bucket (keys
    padded with NaN already are left as they are) and the outputs keep the
    pads: the caller cuts them on the host, so no program is compiled per
    exact length.  The right side stays exact-shape (one build, and one jit
    specialisation, per broadcast dim table).  NaN left keys probe as misses
    on every backend."""
    r_sorted = jnp.asarray(r_sorted, jnp.float32)
    l_keys = jnp.asarray(l_keys, jnp.float32)
    m = int(r_sorted.shape[0])
    if m == 0:
        raise ValueError("join_probe_padded: empty right side (caller gates)")
    n = l_keys.shape[0]
    lp = _pad1(l_keys, pad_len(n), jnp.nan)
    b = backend()
    if b == "xla":
        pos, hit = _join_probe_xla(r_sorted, lp, m)
    else:
        pos, hit = merge_probe(lp, r_sorted, interpret=(b == "interpret"))
    return pos, hit


# -- batched groupby partials -------------------------------------------------


def _segment_batch_body(
    keys: jnp.ndarray,  # int32[n]
    values: Tuple[jnp.ndarray, ...],  # S × f32[n]
    valids: Tuple[jnp.ndarray, ...],  # V × bool[n]
    num_buckets: int,
    modes: Tuple[str, ...],  # len S, "sum" | "min" | "max"
    valid_idx: Tuple[int, ...],  # len S, value row -> valid row
    tile: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All of a groupby's reductions in one dispatch, via lax.scan over row
    tiles.  Per tile the bucket one-hot is built once; every sum-mode row and
    every count row rides the same (rows × T) @ (T × buckets) contraction
    (the MXU on a TPU), with temporaries cache-resident instead of
    n-sized.  min/max rows use a
    masked select + reduce on the same one-hot (no scatter: XLA:CPU scatter
    is serial and catastrophically slow)."""
    n = keys.shape[0]
    nt = n // tile
    kt = keys.reshape(nt, tile)
    vt = tuple(v.reshape(nt, tile) for v in values)
    mt = tuple(m.reshape(nt, tile) for m in valids)
    S, V = len(values), len(valids)
    sum_rows = tuple(i for i, mo in enumerate(modes) if mo == "sum")
    iota = jnp.arange(num_buckets, dtype=jnp.int32)

    mm_rows = tuple(i for i, mo in enumerate(modes) if mo in ("min", "max"))

    def body(acc, inp):
        ki, vi, mi = inp
        sums, cnts, minmax = acc
        ohb = ki[:, None] == iota[None, :]  # (T, nb) bool
        oh = ohb.astype(jnp.float32)
        mf = [m.astype(jnp.float32) for m in mi]
        gemm_rows = [vi[s] * mf[valid_idx[s]] for s in sum_rows] + mf
        # HIGHEST: the TPU's default f32 matmul rounds operands to bf16
        acc_rows = jnp.matmul(  # (len(sum_rows)+V, nb)
            jnp.stack(gemm_rows), oh, precision=jax.lax.Precision.HIGHEST
        )
        sums = sums + acc_rows[: len(sum_rows)]
        cnts = cnts + acc_rows[len(sum_rows):]
        mms = []
        for j, s in enumerate(mm_rows):
            hit = ohb & mi[valid_idx[s]][:, None]
            if modes[s] == "min":
                contrib = jnp.where(hit, vi[s][:, None], jnp.inf).min(0)
                mms.append(jnp.minimum(minmax[j], contrib))
            else:
                contrib = jnp.where(hit, vi[s][:, None], -jnp.inf).max(0)
                mms.append(jnp.maximum(minmax[j], contrib))
        return (sums, cnts, tuple(mms)), None

    init = (
        jnp.zeros((len(sum_rows), num_buckets), jnp.float32),
        jnp.zeros((V, num_buckets), jnp.float32),
        tuple(
            jnp.full(num_buckets, jnp.inf if modes[s] == "min" else -jnp.inf,
                     jnp.float32)
            for s in mm_rows
        ),
    )
    (sums, cnts, minmax), _ = jax.lax.scan(body, init, (kt, vt, mt))
    by_row = {s: sums[j] for j, s in enumerate(sum_rows)}
    by_row.update({s: minmax[j] for j, s in enumerate(mm_rows)})
    reds = (
        jnp.stack([by_row[s] for s in range(S)])
        if S
        else jnp.zeros((0, num_buckets), jnp.float32)
    )
    return reds, cnts


_segment_batch_xla = functools.partial(jax.jit, static_argnames=(
    "num_buckets", "modes", "valid_idx", "tile"))(
    obs.device_scope("segment_reduce")(_segment_batch_body)
)


def segment_reduce_batch(
    keys,
    values: Sequence,  # S value rows, f32[n]
    valids: Sequence,  # V validity rows, bool[n]
    num_buckets: int,
    modes: Sequence[str],  # len S
    valid_idx: Sequence[int],  # len S, value row -> valid row
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched segment reduction: every agg of one groupby in one call.

    Returns ``(reds (S, nb), counts (V, nb))`` where ``reds[s]`` reduces
    ``values[s]`` over ``keys`` restricted to ``valids[valid_idx[s]]`` with
    ``modes[s]``, and ``counts[v]`` counts valid rows per bucket.  Validity
    rows are shared (deduplicated by the caller) so unmasked agg columns do
    not pay for per-column count passes.  Rows pad to a shared shape bucket.
    """
    keys = jnp.asarray(keys, jnp.int32)
    n = keys.shape[0]
    nb = pad_len(n)
    keys = _pad1(keys, nb, 0)
    values = tuple(_pad1(jnp.asarray(v, jnp.float32), nb, 0.0) for v in values)
    valids = tuple(_pad1(jnp.asarray(m, bool), nb, False) for m in valids)
    b = backend()
    if b == "xla":
        # exact bucket count: the GEMM width is the dominant cost and XLA
        # needs no lane alignment (the pallas kernel pads buckets to lanes).
        # Row length pads to a fixed-_TILE tile for the same bucket-invariance
        # reason as masked_stats_batch: padded rows (key 0, valid False) are
        # exact-neutral in the one-hot GEMM and min/max selects, so the fused
        # filter→groupby composite (which reduces at the parent's bucket)
        # stays bit-for-bit with this unfused path (filtered bucket).
        if nb < _TILE:
            pad = _TILE - nb
            keys = jnp.pad(keys, (0, pad))
            values = tuple(jnp.pad(v, (0, pad)) for v in values)
            valids = tuple(
                jnp.pad(m, (0, pad), constant_values=False) for m in valids
            )
            nb = _TILE
        reds, cnts = _segment_batch_xla(
            keys, values, valids, int(num_buckets),
            tuple(modes), tuple(int(i) for i in valid_idx), _TILE,
        )
        return reds, cnts
    interp = b == "interpret"
    red_rows = [
        _segment_pallas(
            keys, values[s], valids[valid_idx[s]], int(num_buckets),
            mode=modes[s], interpret=interp,
        )[0][:num_buckets]
        for s in range(len(values))
    ]
    cnt_rows = [
        _segment_pallas(
            keys, jnp.zeros_like(keys, jnp.float32), valids[v], int(num_buckets),
            mode="sum", interpret=interp,
        )[1][:num_buckets]
        for v in range(len(valids))
    ]
    reds = jnp.stack(red_rows) if red_rows else jnp.zeros((0, num_buckets))
    return reds, jnp.stack(cnt_rows)


# --------------------------------------------------------------------------- #
# Multi-partition fused batches                                                #
#                                                                              #
# The padded entry points above amortise *recompiles* across partitions but    #
# still cost one host→device round-trip per partition — the dispatch-bound     #
# regime that starves the background loop.  The ``*_parts`` wrappers fuse k    #
# same-bucket partitions into ONE dispatch via ``jax.lax.map`` over the        #
# stacked per-partition inputs.  lax.map runs the *identical* per-partition    #
# computation as a device-side loop (not a vmapped/reassociated variant), so   #
# every partition's result is bit-for-bit what the unbatched entry point       #
# returns — the property the frame layer's batched/unbatched parity tests pin  #
# down.  Callers group partitions by shape bucket (`pad_len`) so one stacked   #
# array and one compiled executable covers the whole batch.                    #
#                                                                              #
# These wrappers never block: they return device arrays, and JAX async         #
# dispatch lets the executor launch the next batch while this one computes.    #
# --------------------------------------------------------------------------- #


@functools.partial(
    jax.jit, static_argnames=("num_buckets", "modes", "valid_idx", "tile")
)
@obs.device_scope("segment_reduce")
def _segment_parts_xla(
    keys: jnp.ndarray,  # int32[P, nb]
    values: Tuple[jnp.ndarray, ...],  # S × f32[P, nb]
    valids: Tuple[jnp.ndarray, ...],  # V × bool[P, nb]
    num_buckets: int,
    modes: Tuple[str, ...],
    valid_idx: Tuple[int, ...],
    tile: int,
):
    return jax.lax.map(
        lambda kvm: _segment_batch_body(
            kvm[0], kvm[1], kvm[2], num_buckets, modes, valid_idx, tile
        ),
        (keys, values, valids),
    )


def segment_reduce_batch_parts(
    keys_parts: Sequence,  # P × int32[n_p]
    values_parts: Sequence[Sequence],  # P × (S × f32[n_p])
    valids_parts: Sequence[Sequence],  # P × (V × bool[n_p])
    num_buckets: int,
    modes: Sequence[str],
    valid_idx: Sequence[int],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """k partitions' batched segment reductions in one dispatch.

    Every partition must share the same shape bucket (``pad_len``) and the
    same agg plan (S, V, modes, valid_idx) — callers group accordingly.
    Returns ``(reds (P, S, nb), counts (P, V, nb))`` device arrays, each
    ``[p]`` slice bit-for-bit equal to :func:`segment_reduce_batch` on that
    partition alone.
    """
    nbs = {pad_len(int(jnp.shape(k)[0])) for k in keys_parts}
    if len(nbs) != 1:
        raise ValueError(f"partitions span shape buckets {sorted(nbs)}; group first")
    nb = nbs.pop()
    keys = jnp.stack([_pad1(jnp.asarray(k, jnp.int32), nb, 0) for k in keys_parts])
    S = len(modes)
    V = len(valids_parts[0])
    values = tuple(
        jnp.stack(
            [_pad1(jnp.asarray(vp[s], jnp.float32), nb, 0.0) for vp in values_parts]
        )
        for s in range(S)
    )
    valids = tuple(
        jnp.stack(
            [_pad1(jnp.asarray(mp[v], bool), nb, False) for mp in valids_parts]
        )
        for v in range(V)
    )
    if backend() == "xla":
        # mirror segment_reduce_batch's fixed-_TILE widening (parity)
        if nb < _TILE:
            pad = ((0, 0), (0, _TILE - nb))
            keys = jnp.pad(keys, pad)
            values = tuple(jnp.pad(v, pad) for v in values)
            valids = tuple(jnp.pad(m, pad, constant_values=False) for m in valids)
        return _segment_parts_xla(
            keys, values, valids, int(num_buckets),
            tuple(modes), tuple(int(i) for i in valid_idx), _TILE,
        )
    # pallas / interpret: no fused path yet — loop per partition (still one
    # call site; correctness-only backends on this container)
    reds_all, cnts_all = [], []
    for p in range(len(keys_parts)):
        reds, cnts = segment_reduce_batch(
            keys_parts[p], list(values_parts[p]), list(valids_parts[p]),
            num_buckets, list(modes), list(valid_idx),
        )
        reds_all.append(reds)
        cnts_all.append(cnts)
    return jnp.stack(reds_all), jnp.stack(cnts_all)


@functools.partial(jax.jit, static_argnames=("k", "largest"))
@obs.device_scope("topk")
def _topk_parts_xla(xs: jnp.ndarray, k: int, largest: bool) -> jnp.ndarray:
    return jax.lax.map(lambda x: _topk_body(x, k, largest), xs)


def _stack_host_padded(rows: Sequence, nb: int, fill, dtype) -> jnp.ndarray:
    """Pad + stack *host* arrays on host, then upload once.  Stacking on
    device instead would cost one transfer per row — exactly the per-dispatch
    overhead the fused entry points exist to amortise."""
    with obs.span("dispatch.prep"):
        out = np.full((len(rows), nb), fill, dtype)
        for i, r in enumerate(rows):
            r = np.asarray(r, dtype)
            out[i, : r.shape[0]] = r
    return upload(out)


def topk_padded_parts(xs_parts: Sequence, k: int, largest: bool = True) -> jnp.ndarray:
    """k partitions' top-k winner values in one dispatch: (P, k) device array,
    each row bit-for-bit :func:`topk_padded` on that partition alone.  All
    partitions must share a shape bucket."""
    nbs = {pad_len(int(np.shape(x)[0])) for x in xs_parts}
    if len(nbs) != 1:
        raise ValueError(f"partitions span shape buckets {sorted(nbs)}; group first")
    nb = nbs.pop()
    sentinel = np.float32(-np.inf if largest else np.inf)
    xs = _stack_host_padded(xs_parts, nb, sentinel, np.float32)
    if backend() == "xla":
        return _topk_parts_xla(xs, k, largest)
    return jnp.stack([topk(xs[p], k, largest=largest) for p in range(xs.shape[0])])


@jax.jit
@obs.device_scope("sort")
def _sort_order_parts_xla(hi: jnp.ndarray, mid: jnp.ndarray, lo: jnp.ndarray):
    return jax.lax.map(lambda t: _sort_order_body(*t), (hi, mid, lo))


def argsort_f64_parts(keys_parts: Sequence) -> jnp.ndarray:
    """k partitions' stable exact-split argsorts in one dispatch: (P, nb)
    int32 device array; row p's first ``len(keys_parts[p])`` entries are
    bit-for-bit :func:`argsort_f64` on that partition alone.  Preconditions
    per partition as for :func:`argsort_f64` (callers gate with
    ``_sort_keys_exact``); all partitions must share a shape bucket."""
    nbs = {pad_len(len(k)) for k in keys_parts}
    if len(nbs) != 1:
        raise ValueError(f"partitions span shape buckets {sorted(nbs)}; group first")
    nb = nbs.pop()
    with obs.span("dispatch.prep"):
        splits = [split_f64(k) for k in keys_parts]
    his = _stack_host_padded([s[0] for s in splits], nb, np.float32(np.inf), np.float32)
    mids = _stack_host_padded([s[1] for s in splits], nb, np.float32(0.0), np.float32)
    los = _stack_host_padded([s[2] for s in splits], nb, np.float32(0.0), np.float32)
    return _sort_order_parts_xla(his, mids, los)


@jax.jit
@obs.device_scope("filter_compact")
def _filter_parts_xla(xs: jnp.ndarray, keeps: jnp.ndarray):
    return jax.lax.map(lambda t: ref.filter_compact_ref(t[0], t[1], 0.0), (xs, keeps))


def filter_compact_padded_parts(
    xs_rows: Sequence, keeps_rows: Sequence
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stacked stable compactions in one dispatch: R rows (columns × batched
    partitions) of values + keep masks → ``(out (R, nb), counts (R,))`` device
    arrays, each row bit-for-bit :func:`filter_compact_padded` on that row
    alone.  All rows must share a shape bucket."""
    nbs = {pad_len(int(jnp.shape(x)[0])) for x in xs_rows}
    if len(nbs) != 1:
        raise ValueError(f"rows span shape buckets {sorted(nbs)}; group first")
    nb = nbs.pop()
    xs = jnp.stack([_pad1(jnp.asarray(x, jnp.float32), nb, 0.0) for x in xs_rows])
    keeps = jnp.stack(
        [_pad1(jnp.asarray(m, bool), nb, False) for m in keeps_rows]
    )
    if backend() == "xla":
        return _filter_parts_xla(xs, keeps)
    outs, cnts = [], []
    for p in range(xs.shape[0]):
        o, c = filter_compact(xs[p], keeps[p], fill=0.0)
        outs.append(o)
        cnts.append(c)
    return jnp.stack(outs), jnp.stack(cnts)


@functools.partial(jax.jit, static_argnames=("tile",))
@obs.device_scope("masked_stats")
def _masked_stats_rows_map_xla(xs: jnp.ndarray, ms: jnp.ndarray, tile: int):
    return jax.lax.map(lambda t: _stats_row_tiled(t[0], t[1], tile), (xs, ms))


def masked_stats_batch_parts(
    xs_rows: Sequence, ms_rows: Sequence
) -> jnp.ndarray:
    """Stacked masked-stats rows (k partitions × C columns) in one dispatch:
    (R, 5) device array.  Each row runs the same ``_stats_row_tiled`` body as
    :func:`masked_stats_batch` — via ``lax.map`` over the stacked leading
    axis, so the compiled body is independent of R (the unrolled form would
    recompile for every distinct fused batch size).  Bit-for-bit per row;
    all rows must share a shape bucket (checked by the concatenate)."""
    xs = jnp.concatenate([jnp.asarray(x, jnp.float32) for x in xs_rows])
    ms = jnp.concatenate([jnp.asarray(m, bool) for m in ms_rows])
    if backend() == "xla" and xs.shape[1] == pad_len(xs.shape[1], minimum=1):
        # mirror masked_stats_batch's fixed-_TILE widening (parity)
        if xs.shape[1] < _TILE:
            pad = ((0, 0), (0, _TILE - xs.shape[1]))
            xs = jnp.pad(xs, pad)
            ms = jnp.pad(ms, pad, constant_values=False)
        return _masked_stats_rows_map_xla(xs, ms, _TILE)
    return masked_stats_batch(xs, ms)


# --------------------------------------------------------------------------- #
# Fused composites: filter→reduce chains lowered as ONE jit'd dispatch         #
#                                                                              #
# The planner (`frame/planner.py`) detects linear chains where a filter's      #
# output feeds exactly one reduction and lowers them here instead of           #
# materialising the intermediate partition: the filtered rows never leave the  #
# device (or, on CPU, never round-trip through host numpy between ops).        #
#                                                                              #
# Bit-for-bit contract vs the unfused sequence: each composite first STABLE-   #
# COMPACTS the kept rows to the array prefix, then runs the very same tiled    #
# reduce body the unfused second stage runs.  Compaction is pure data          #
# movement — any algorithm producing the same permutation is byte-identical   #
# — so the fused path uses the *fast* formulation: the kept-row indices come  #
# from a host `np.flatnonzero` over the keep mask (which is host-resident     #
# anyway, produced by predicate evaluation), and the jit body GATHERS rows    #
# into prefix position.  On CPU XLA a gather is ~100× cheaper than the        #
# equivalent 1M-row scatter, which is what makes the fused chain beat the     #
# two-dispatch plan instead of losing to it.  Because both reduce paths use   #
# fixed-_TILE tiles (see masked_stats_batch / segment_reduce_batch), the kept #
# values occupy identical positions in identical-width tiles on both paths    #
# and the trailing all-padding tiles are exact-neutral — so the fused result  #
# equals the unfused result to the bit, not merely to tolerance.  Shapes stay #
# inside the same power-of-two bucket universe (`pad_len`), so fusion adds no #
# new compilation cache pressure.                                              #
# --------------------------------------------------------------------------- #


def _compact_gather_idx(keep, nb: int) -> np.ndarray:
    """Host-side compaction index: ``idx[j]`` = source row of compacted slot
    ``j`` (ascending, so the gather is stable), padded with ``nb`` (out of
    range → the gather's fill value, i.e. the compaction's pad)."""
    kept = np.flatnonzero(np.asarray(keep, bool))
    idx = np.full(nb, nb, np.int32)
    idx[: kept.size] = kept
    return idx


@functools.partial(jax.jit, static_argnames=("tile",))
@obs.device_scope("filter_then_masked_stats")
def _filter_stats_xla(
    xs: jnp.ndarray, ms: jnp.ndarray, idx: jnp.ndarray, tile: int
) -> jnp.ndarray:
    def one(args):
        x, m = args
        xc = x.at[idx].get(mode="fill", fill_value=0.0)
        mc = m.at[idx].get(mode="fill", fill_value=False)
        return _stats_row_tiled(xc, mc, tile)

    return jax.lax.map(one, (xs, ms))


def filter_then_masked_stats(xs, ms, keep) -> jnp.ndarray:
    """Fused filter→describe: (C, n) values + (C, n) validity + keep (host
    bool mask over the first ≤ n rows) → (C, 5) rows of (count, sum, m2,
    min, max) over the kept+valid entries.

    Bit-for-bit equal to ``masked_stats_batch`` on the filtered partition
    (i.e. compact first on the host, then reduce) — the compaction runs as
    an in-jit gather instead, so the chain is one dispatch with no
    intermediate materialisation."""
    xs = jnp.asarray(xs, jnp.float32)
    ms = jnp.asarray(ms, bool)
    c, n = xs.shape
    nb = pad_len(n)
    if backend() == "xla":
        nb = max(nb, _TILE)
    with obs.span("dispatch.prep"):
        idx = _compact_gather_idx(keep, nb)
    if nb != n:
        xs = jnp.pad(xs, ((0, 0), (0, nb - n)))
        ms = jnp.pad(ms, ((0, 0), (0, nb - n)), constant_values=False)
    if backend() == "xla":
        return _filter_stats_xla(xs, ms, upload(idx), _TILE)
    # interpret / pallas: compact via the reference scatter math, reduce via
    # the backend's own stats path (correctness-only backends here)
    keep_dev = _pad1(upload(keep, bool), nb, False)
    rows = []
    for i in range(c):
        xc, _ = ref.filter_compact_ref(xs[i], keep_dev, 0.0)
        mc, _ = ref.filter_compact_ref(ms[i].astype(jnp.float32), keep_dev, 0.0)
        rows.append((xc, mc > 0.5))
    return masked_stats_batch(
        jnp.stack([r[0] for r in rows]), jnp.stack([r[1] for r in rows])
    )


@functools.partial(
    jax.jit, static_argnames=("num_buckets", "modes", "valid_idx", "tile")
)
@obs.device_scope("filter_then_segment_reduce")
def _filter_segment_xla(
    keys: jnp.ndarray,  # i32[n] group codes
    values: Tuple[jnp.ndarray, ...],
    valids: Tuple[jnp.ndarray, ...],
    idx: jnp.ndarray,
    num_buckets: int,
    modes: Tuple[str, ...],
    valid_idx: Tuple[int, ...],
    tile: int,
):
    keys_c = keys.at[idx].get(mode="fill", fill_value=0)
    vals_c = tuple(v.at[idx].get(mode="fill", fill_value=0.0) for v in values)
    mins_c = tuple(m.at[idx].get(mode="fill", fill_value=False) for m in valids)
    return _segment_batch_body(
        keys_c, vals_c, mins_c, num_buckets, modes, valid_idx, tile
    )


def filter_then_segment_reduce(
    keys,
    values: Sequence,
    valids: Sequence,
    keep,
    num_buckets: int,
    modes: Sequence[str],
    valid_idx: Sequence[int],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused filter→groupby: segment reductions over the kept rows only, in
    one dispatch.  Same contract as ``segment_reduce_batch`` on the filtered
    partition, bit-for-bit (stable gather compaction; padded rows carry key 0
    with valid False, exact-neutral in the one-hot GEMM).  ``keep`` is the
    host bool mask (see the section comment — the compaction indices are
    computed host-side).

    ``num_buckets`` bounds the one-hot GEMM width; callers gate it below
    2**24 (beyond which the reduction matrix stops being a sane dispatch)."""
    if int(num_buckets) >= 1 << 24:
        raise ValueError("filter_then_segment_reduce: num_buckets too large (gate)")
    keys = jnp.asarray(keys, jnp.int32)
    n = keys.shape[0]
    nb = pad_len(n)
    if backend() == "xla":
        nb = max(nb, _TILE)
    with obs.span("dispatch.prep"):
        idx = _compact_gather_idx(keep, nb)
    keys = _pad1(keys, nb, 0)
    values = tuple(_pad1(jnp.asarray(v, jnp.float32), nb, 0.0) for v in values)
    valids = tuple(_pad1(jnp.asarray(m, bool), nb, False) for m in valids)
    if backend() == "xla":
        return _filter_segment_xla(
            keys, values, valids, upload(idx), int(num_buckets),
            tuple(modes), tuple(int(i) for i in valid_idx), _TILE,
        )
    keep_dev = _pad1(upload(keep, bool), nb, False)
    keys_c = ref.filter_compact_ref(keys.astype(jnp.float32), keep_dev, 0.0)[0]
    vals_c = [ref.filter_compact_ref(v, keep_dev, 0.0)[0] for v in values]
    mins_c = [
        ref.filter_compact_ref(m.astype(jnp.float32), keep_dev, 0.0)[0] > 0.5
        for m in valids
    ]
    return segment_reduce_batch(
        keys_c.astype(jnp.int32), vals_c, mins_c, num_buckets, modes, valid_idx
    )


@functools.partial(jax.jit, static_argnames=("k", "largest"))
@obs.device_scope("filter_then_topk")
def _topk_masked_xla(
    x: jnp.ndarray, keep: jnp.ndarray, k: int, largest: bool
) -> jnp.ndarray:
    sentinel = -jnp.inf if largest else jnp.inf
    return _topk_body(jnp.where(keep, x, sentinel), k, largest)


def topk_masked_padded(x, keep, k: int, largest: bool = True) -> jnp.ndarray:
    """Fused filter→topk winner values: ``topk`` restricted to kept rows,
    without compacting — masked-out rows take the losing sentinel inside the
    jit.  ``lax.top_k`` returns *values*, so the result equals
    ``topk_padded`` on the compacted kept rows exactly (same value multiset,
    sentinels lose; callers gate kept-count > k so no sentinel wins)."""
    x = _device(x, jnp.float32)
    keep = _device(keep, bool)
    nb = pad_len(x.shape[0])
    sentinel = -jnp.inf if largest else jnp.inf
    xp = _pad1(x, nb, sentinel)
    kp = _pad1(keep, nb, False)
    if backend() == "xla":
        return _topk_masked_xla(xp, kp, k, largest)
    return topk(jnp.where(kp, xp, sentinel), k, largest=largest)


# Shard-local reuse (frame/dist.py): the per-partition tiled bodies double as
# the per-shard kernels inside one shard_map dispatch — sharded combines stay
# bit-identical to the host path only because the *same* traced scan produces
# the per-partition raws on both sides.
stats_row_tiled = _stats_row_tiled
segment_batch_body = _segment_batch_body
topk_body = _topk_body
TILE = _TILE
