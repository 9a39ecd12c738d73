"""Pallas kernel validation: shape/dtype sweeps vs. the ref.py oracles.

Kernels run in interpret mode (CPU container; Mosaic targets real TPUs).
"""
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.filter_compact import filter_compact
from repro.kernels.join_probe import BANDS, band_order, join_probe
from repro.kernels.masked_stats import masked_stats
from repro.kernels.segment_reduce import segment_reduce
from repro.kernels.topk import topk

RNG = np.random.default_rng(42)
JP = importlib.import_module("repro.kernels.join_probe")  # the module, not the function


# ------------------------------------------------------------- segment_reduce --
@pytest.mark.parametrize("n,nb", [(100, 7), (3000, 37), (5000, 200), (512, 128)])
@pytest.mark.parametrize("mode", ["sum", "min", "max"])
def test_segment_reduce_sweep(n, nb, mode):
    keys = jnp.asarray(RNG.integers(0, nb, n), jnp.int32)
    vals = jnp.asarray(RNG.normal(size=n), jnp.float32)
    valid = jnp.asarray(RNG.uniform(size=n) > 0.25)
    out, cnt = segment_reduce(keys, vals, valid, nb, mode=mode, interpret=True)
    rout, rcnt = R.segment_reduce_ref(keys, vals, valid, nb, mode=mode)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout), atol=1e-4)
    np.testing.assert_allclose(np.asarray(cnt), np.asarray(rcnt))


def test_segment_reduce_empty_buckets():
    keys = jnp.asarray([0, 0, 5], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
    valid = jnp.ones(3, bool)
    out, cnt = segment_reduce(keys, vals, valid, 8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), [3, 0, 0, 0, 0, 3, 0, 0])


# --------------------------------------------------------------- masked_stats --
@pytest.mark.parametrize("n", [10, 1000, 4096, 5001])
@pytest.mark.parametrize("null_frac", [0.0, 0.3])
def test_masked_stats_sweep(n, null_frac):
    x = jnp.asarray(RNG.normal(size=n) * 10, jnp.float32)
    m = jnp.asarray(RNG.uniform(size=n) >= null_frac)
    out = masked_stats(x, m, interpret=True)
    ref = R.masked_stats_ref(x, m)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-2
    )


# -------------------------------------------------------------- filter_compact --
@pytest.mark.parametrize("n", [64, 1000, 4096])
@pytest.mark.parametrize("sel", [0.0, 0.5, 1.0])
def test_filter_compact_sweep(n, sel):
    x = jnp.asarray(RNG.normal(size=n), jnp.float32)
    keep = jnp.asarray(RNG.uniform(size=n) < sel)
    out, cnt = filter_compact(x, keep, interpret=True)
    rout, rcnt = R.filter_compact_ref(x, keep)
    assert int(cnt) == int(rcnt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout), atol=1e-6)


# 3,500 values at 8 rows of 128 lanes a tile: 28 rows of data, 4 tiles
BITS_N, BITS_TILE = 3500, 8
SPECIALS = np.array([0x7FC01234, 0xFFC00ABC, 0x7F800000, 0xFF800000, 0x80000000],
                    np.uint32).view(np.float32)  # NaNs with payloads, ±inf, −0.0


KEEP_CASES = ("sel0", "sel1", "sel0.03", "sel0.5", "one_per_tile",
              "last_row_only", "runs")


def _keep_case(case: str) -> np.ndarray:
    rng = np.random.default_rng(KEEP_CASES.index(case))
    keep = np.zeros(BITS_N, bool)
    if case in ("sel0", "sel1", "sel0.03", "sel0.5"):
        keep = rng.uniform(size=BITS_N) < float(case[3:])
    elif case == "one_per_tile":
        keep[np.arange(4) * BITS_TILE * 128 + 300] = True
    elif case == "last_row_only":
        keep[27 * 128:][rng.uniform(size=BITS_N - 27 * 128) < 0.5] = True
    else:  # runs across row (256) and tile (1024, 2048) boundaries, whole rows
        for a, b in ((250, 270), (1000, 1100), (2040, 2060), (2500, 2900)):
            keep[a:b] = True
    return keep


@pytest.mark.parametrize("fill", [0.0, -7.5])
@pytest.mark.parametrize("case", KEEP_CASES)
def test_filter_compact_bits_match_ref(case, fill):
    x = np.random.default_rng(7).normal(size=BITS_N).astype(np.float32)
    x[::7] = np.resize(SPECIALS, x[::7].shape)
    keep = _keep_case(case)
    out, cnt = filter_compact(jnp.asarray(x), jnp.asarray(keep),
                              tile_rows=BITS_TILE, fill=fill, interpret=True)
    rout, rcnt = R.filter_compact_ref(jnp.asarray(x), jnp.asarray(keep), fill)
    out = np.asarray(out)
    assert int(cnt) == int(rcnt) == int(keep.sum())
    np.testing.assert_array_equal(out.view(np.uint32), np.asarray(rout).view(np.uint32))
    np.testing.assert_array_equal(out[:int(cnt)].view(np.uint32), x[keep].view(np.uint32))
    assert (out[int(cnt):] == np.float32(fill)).all()


# ------------------------------------------------------------------------ topk --
# --------------------------------------------------------------- join probe --
EXACT = (1 << 24) - 1  # largest integer key f32 holds exactly


def _unique_keys(m, rng):
    """``m`` distinct integer keys in [-(2^24 - 1), 2^24 - 1] as f32, both
    ends included, ascending."""
    keys = {-EXACT, EXACT}
    while len(keys) < m:
        keys.update(rng.integers(-EXACT, EXACT + 1, m).tolist())
    return np.sort(np.array(sorted(keys)[:m], np.float32))


def _probe_in_row_order(l, r, **kw):
    """The probe's band-order outputs put back in row order."""
    pos, hit, perm = (np.asarray(a) for a in join_probe(
        jnp.asarray(l), jnp.asarray(r), interpret=True, **kw))
    assert sorted(perm.tolist()) == list(range(len(l)))  # a permutation
    p, h = np.empty_like(pos), np.empty_like(hit)
    p[perm], h[perm] = pos, hit
    return p, h


@pytest.mark.parametrize("m", [1, 127, 2048, 2049, 50_000])
@pytest.mark.parametrize("n", [100, 5_000])
def test_join_probe_matches_searchsorted(m, n):
    """Positions are searchsorted-left and hits exact matches, for left keys
    present, absent, at ±(2^24 - 1), ±inf and NaN (which count nothing and
    match nothing), whatever the right side's size against its blocks."""
    rng = np.random.default_rng(m * 7 + n)
    r = _unique_keys(m, rng)
    if m > 2:
        r = np.concatenate([[-np.inf], r[1:-1], [np.inf]]).astype(np.float32)
    l = np.concatenate([
        rng.choice(r, n // 2),  # present
        rng.integers(-EXACT, EXACT + 1, n - n // 2 - 6).astype(np.float32),
        [np.nan, np.inf, -np.inf, EXACT, -EXACT, EXACT - 1],
    ]).astype(np.float32)
    rng.shuffle(l)
    pos, hit = _probe_in_row_order(l, r)
    want = np.searchsorted(r, l, side="left")
    want[np.isnan(l)] = 0
    np.testing.assert_array_equal(pos, want)
    np.testing.assert_array_equal(hit, np.isin(l, r))
    assert hit[np.isin(l, r)].all() and not hit[np.isnan(l)].any()


@pytest.mark.parametrize("right_block", [128, 2048])
def test_join_probe_blocks_and_tiles(right_block):
    """Dense left keys (many tiles in one right block) and sparse ones (one
    tile across many blocks), NaN pads to the bucket, under small and large
    right blocks and one tile per grid step: the same answers."""
    rng = np.random.default_rng(right_block)
    r = np.arange(0, 30_000, 3, dtype=np.float32)  # 10,000 keys, every third
    dense = rng.integers(0, 300, 6_000).astype(np.float32)
    sparse = rng.integers(-10, 30_010, 700).astype(np.float32)
    l = np.concatenate([dense, sparse, np.full(300, np.nan, np.float32)])
    pos, hit = _probe_in_row_order(l, r, right_block=right_block, tiles_per_step=1)
    want = np.searchsorted(r, l, side="left")
    want[np.isnan(l)] = 0
    np.testing.assert_array_equal(pos, want)
    np.testing.assert_array_equal(hit, np.isin(l, r))


def test_join_probe_sorts_and_pads():
    """The outputs come in band order, one entry a left key, and the bucket's
    pads are cut; the sweep leaves a key above every right key at m."""
    l = np.array([5.0, 1.0, 9.0, 3.0], np.float32)
    r = np.array([1.0, 3.0, 5.0], np.float32)
    pos, hit, perm = (np.asarray(a) for a in join_probe(
        jnp.asarray(l), jnp.asarray(r), interpret=True))
    assert perm.tolist() == [1, 3, 0, 2]
    assert pos.tolist() == [0, 1, 2, 3] and hit.tolist() == [1, 1, 1, 0]


@pytest.mark.parametrize("keys", [
    "uniform", "outliers", "equal", "inf_and_nan", "all_nan", "empty", "chunks"])
def test_join_probe_band_order(keys, monkeypatch):
    """The host's ordering: a permutation, with the keys in its order, that
    takes them in ascending bands of equal width over the right side's
    range (a band's keys keep their row order), keys below it, ``-inf`` and
    NaN first, keys above it and ``+inf`` last; past ``2 · ORDER_CHUNK``
    keys each chunk so, one after another."""
    rng = np.random.default_rng(len(keys))
    lo, hi = 1.0, 1_500_000.0
    k = {
        "uniform": rng.integers(1, 1_500_001, 20_000).astype(np.float32),
        "outliers": np.concatenate([rng.integers(1, 1_500_001, 5_000),
                                    [2 ** 24 - 1, -(2 ** 24 - 1)]]).astype(np.float32),
        "equal": np.full(300, 7.0, np.float32),
        "inf_and_nan": np.array([3e6, np.nan, -np.inf, 1, np.inf, 2e5, np.nan], np.float32),
        "all_nan": np.full(5, np.nan, np.float32),
        "empty": np.zeros(0, np.float32),
        "chunks": rng.integers(1, 1_500_001, 10_000),  # int64, as a column holds
    }[keys]
    if keys == "chunks":
        monkeypatch.setattr(JP, "ORDER_CHUNK", 3_000)  # three chunks
    perm, ordered = band_order(k, lo, hi)
    assert sorted(perm.tolist()) == list(range(len(k)))
    np.testing.assert_array_equal(ordered, k[perm].astype(np.float32))
    kf = k.astype(np.float32)
    band = np.floor(np.clip(np.nan_to_num((kf - np.float32(lo)) * np.float32(
        (BANDS - 1) / (hi - lo)), nan=0.0), 0, BANDS - 1))
    cuts = np.linspace(0, len(k), 4).astype(int) if keys == "chunks" else [0, len(k)]
    for a, b in zip(cuts[:-1], cuts[1:]):
        got = band[perm[a:b]]
        assert (np.diff(got) >= 0).all()  # ascending bands
        for v in np.unique(got):  # row order within a band
            assert (np.diff(perm[a:b][got == v]) > 0).all()
        assert sorted(perm[a:b].tolist()) == list(range(a, b))
    if keys == "inf_and_nan":
        assert set(perm[:4].tolist()) == {1, 2, 3, 6} and perm[-1] == 4


@pytest.mark.parametrize("n,k", [(100, 1), (4000, 7), (4000, 64), (999, 10)])
@pytest.mark.parametrize("largest", [True, False])
def test_topk_sweep(n, k, largest):
    x = jnp.asarray(RNG.normal(size=n), jnp.float32)
    out = topk(x, k, largest=largest, interpret=True)
    ref = R.topk_ref(x, k, largest=largest)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))
