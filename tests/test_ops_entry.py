"""The ``kernels/ops.py`` entry points that ``frame/backend.py`` calls, at
the lengths where their shape buckets change: one row, either side of
``PAD_MIN`` and one row past the XLA path's scan tile (``TILE``).

Each case runs under the ``xla`` backend and, where the entry point has a
Pallas path, under Pallas interpret mode, and is checked against numpy or
``kernels/ref.py``: to the bit where the path is exact, otherwise within
the tolerance that ``test_kernels.py`` gives the same operator.  The batched
``*_parts`` entry points and the fused filter→reduce composites are in
``test_ops_entry_parts.py``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R

LENGTHS = (1, ops.PAD_MIN - 1, ops.PAD_MIN + 1, ops.TILE + 1)
EXACT = (1 << 24) - 1  # largest integer key f32 holds exactly


def _bits(a) -> np.ndarray:
    return np.asarray(a, np.float32).view(np.uint32)


# ------------------------------------------------------------------- pad_len --
@pytest.mark.parametrize("n,minimum,bucket", [
    (0, 512, 512), (1, 512, 512), (511, 512, 512), (512, 512, 512),
    (513, 512, 1024), (16384, 512, 16384), (16385, 512, 32768),
    ((1 << 22) + 1, 512, 1 << 23),
    (1, 1, 1), (2, 1, 2), (3, 1, 4), (513, 1, 1024),
])
def test_pad_len_table(n, minimum, bucket):
    assert ops.pad_len(n, minimum=minimum) == bucket


# ------------------------------------------------------------ filter_compact --
@pytest.mark.parametrize("sel", [0.0, 0.5, 1.0])
@pytest.mark.parametrize("n", LENGTHS)
def test_filter_compact_padded(ops_backend, n, sel):
    rng = np.random.default_rng([n, 1])
    x = rng.normal(size=n).astype(np.float32)
    keep = rng.uniform(size=n) < sel
    out, cnt = ops.filter_compact_padded(x, keep)
    want = np.zeros(n, np.float32)
    want[: keep.sum()] = x[keep]
    assert np.shape(out) == (n,) and int(cnt) == int(keep.sum())
    np.testing.assert_array_equal(_bits(out), _bits(want))


# -------------------------------------------------------------- masked_stats --
def _stats_rows(n, c, seed):
    """``c`` rows of scaled normals with 30% nulls; the last row all null."""
    rng = np.random.default_rng([n, seed])
    xs = (rng.normal(size=(c, n)) * 10).astype(np.float32)
    ms = rng.uniform(size=(c, n)) >= 0.3
    ms[-1] = False
    return xs, ms


def _stats_ref(x, m) -> np.ndarray:
    return np.asarray(R.masked_stats_ref(jnp.asarray(x), jnp.asarray(m)))


@pytest.mark.parametrize("n", LENGTHS)
def test_masked_stats(ops_backend, n):
    xs, ms = _stats_rows(n, 2, 2)
    for x, m in zip(xs, ms):
        out = ops.masked_stats(jnp.asarray(x), jnp.asarray(m))
        np.testing.assert_allclose(
            np.asarray(out), _stats_ref(x, m), rtol=1e-4, atol=1e-2
        )


@pytest.mark.parametrize("n", LENGTHS)
def test_masked_stats_batch(ops_backend, n):
    xs, ms = _stats_rows(n, 3, 3)
    out = np.asarray(ops.masked_stats_batch(xs, ms))
    assert out.shape == (3, 5)
    for i in range(3):
        np.testing.assert_allclose(out[i], _stats_ref(xs[i], ms[i]), rtol=1e-4, atol=1e-2)
    assert out[-1].tolist() == [0.0, 0.0, 0.0, np.inf, -np.inf]


# ------------------------------------------------------------ segment_reduce --
NUM_BUCKETS = 200  # keys land on even buckets only: every odd one is empty


def _groupby_inputs(n, seed):
    rng = np.random.default_rng([n, seed])
    keys = (2 * rng.integers(0, NUM_BUCKETS // 2, n)).astype(np.int32)
    values = [rng.normal(size=n).astype(np.float32) for _ in range(2)]
    valids = [rng.uniform(size=n) > 0.25, np.ones(n, bool)]
    return keys, values, valids


def _segment_ref(keys, v, m, mode):
    red, cnt = R.segment_reduce_ref(
        jnp.asarray(keys), jnp.asarray(v), jnp.asarray(m), NUM_BUCKETS, mode
    )
    return np.asarray(red), np.asarray(cnt)


@pytest.mark.parametrize("mode", ["sum", "min", "max"])
@pytest.mark.parametrize("n", LENGTHS)
def test_segment_reduce_batch(ops_backend, n, mode):
    """One groupby plan as the frame layer builds it: a ``mode`` row on the
    second validity row, a sum row on the first, and a count per validity
    row, over 200 buckets of which 100 receive no row."""
    keys, values, valids = _groupby_inputs(n, 4)
    reds, cnts = ops.segment_reduce_batch(
        keys, values, valids, NUM_BUCKETS, [mode, "sum"], [1, 0]
    )
    reds, cnts = np.asarray(reds), np.asarray(cnts)
    assert reds.shape == (2, NUM_BUCKETS) and cnts.shape == (2, NUM_BUCKETS)
    for row, (v, m, mo) in enumerate(
        [(values[0], valids[1], mode), (values[1], valids[0], "sum")]
    ):
        np.testing.assert_allclose(reds[row], _segment_ref(keys, v, m, mo)[0], atol=1e-4)
    for row, m in enumerate(valids):
        np.testing.assert_array_equal(cnts[row], _segment_ref(keys, values[0], m, "sum")[1])
    assert not cnts[:, 1::2].any()


# ---------------------------------------------------------------------- topk --
def _topk_want(x, k, largest) -> np.ndarray:
    """numpy's top ``k``, best first, the losing sentinel past ``len(x)``."""
    s = np.sort(x)
    s = s[::-1] if largest else s
    pad = np.full(max(k - len(x), 0), -np.inf if largest else np.inf, np.float32)
    return np.concatenate([s[:k], pad]).astype(np.float32)


@pytest.mark.parametrize("k", [8, 128])
@pytest.mark.parametrize("largest", [True, False])
@pytest.mark.parametrize("n", LENGTHS)
def test_topk_padded(ops_backend, n, largest, k):
    """Ties included (values on a grid of 64); at one row ``k`` exceeds
    ``n`` and the bucket's sentinels fill the rest."""
    rng = np.random.default_rng([n, 5])
    x = rng.integers(-32, 32, n).astype(np.float32)
    out = ops.topk_padded(x, k, largest=largest)
    np.testing.assert_array_equal(np.asarray(out), _topk_want(x, k, largest))


@pytest.mark.parametrize("largest", [True, False])
@pytest.mark.parametrize("n", LENGTHS)
def test_topk_masked_padded(ops_backend, n, largest):
    rng = np.random.default_rng([n, 6])
    x = rng.normal(size=n).astype(np.float32)
    keep = rng.uniform(size=n) < 0.5
    out = ops.topk_masked_padded(x, keep, 8, largest=largest)
    np.testing.assert_array_equal(np.asarray(out), _topk_want(x[keep], 8, largest))


# ---------------------------------------------------------------- argsort_f64 --
def _sort_keys(kind, n):
    rng = np.random.default_rng([n, 7])
    if kind == "ties":
        return rng.integers(-3, 3, n).astype(np.float64)
    if kind == "null_sentinels":
        keys = rng.normal(size=n) * 1e3
        keys[rng.uniform(size=n) < 0.2] = np.inf
        keys[rng.uniform(size=n) < 0.2] = -np.inf
        return keys
    # equal hi and mid components: 1 + 2^-25 + j·2^-52 for j < 8 differ only in lo
    j = rng.integers(0, 8, n)
    return np.where(rng.uniform(size=n) < 0.5, 1.0, -1.0) * (1.0 + 2.0**-25 + j * 2.0**-52)


@pytest.mark.parametrize("kind", ["ties", "null_sentinels", "lo_only"])
@pytest.mark.parametrize("n", LENGTHS)
def test_argsort_f64(n, kind):
    """Once per length and key kind: every backend shares the jit'd ``lax.sort``."""
    keys = _sort_keys(kind, n)
    hi, mid, lo = ops.split_f64(keys)
    finite = np.isfinite(keys)
    recon = hi.astype(np.float64) + mid.astype(np.float64) + lo.astype(np.float64)
    np.testing.assert_array_equal(recon[finite], keys[finite])
    assert not mid[~finite].any() and not lo[~finite].any()
    if kind == "lo_only":
        assert np.unique(np.abs(hi)).size == 1 and np.unique(np.abs(mid)).size == 1
    got = np.asarray(ops.argsort_f64(keys))
    np.testing.assert_array_equal(got, np.argsort(keys, kind="stable"))


# ----------------------------------------------------------------- join probe --
@pytest.mark.parametrize("n", LENGTHS)
def test_join_probe_padded(ops_backend, n):
    """Left keys present, missing, and at ±(2^24 - 1) on both sides; the
    outputs keep the bucket's pads, which miss."""
    rng = np.random.default_rng([n, 8])
    r = np.unique(np.concatenate([
        [-EXACT, EXACT], rng.integers(-EXACT, EXACT + 1, 3000)
    ])).astype(np.float32)
    l = np.concatenate([
        [EXACT, -EXACT, EXACT - 1][: n],
        np.where(rng.uniform(size=max(n - 3, 0)) < 0.5,
                 rng.choice(r, max(n - 3, 0)),
                 rng.integers(-EXACT, EXACT + 1, max(n - 3, 0))),
    ]).astype(np.float32)
    rng.shuffle(l)
    pos, hit = (np.asarray(a) for a in ops.join_probe_padded(r, l))
    assert len(pos) == len(hit) >= ops.pad_len(n)
    np.testing.assert_array_equal(pos[:n], np.searchsorted(r, l, side="left"))
    np.testing.assert_array_equal(hit[:n], np.isin(l, r))
    assert not hit[n:].any()
