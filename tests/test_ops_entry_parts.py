"""The batched ``*_parts`` entry points and the fused filter→reduce
composites of ``kernels/ops.py``, as ``frame/backend.py`` calls them, at the
shape buckets either side of ``PAD_MIN`` and past the XLA path's scan tile.

Each ``*_parts`` call must give, partition by partition, to the bit, what
its per-partition counterpart gives that partition alone; each composite,
what the unfused filter-then-reduce sequence gives, and both must agree
with ``kernels/ref.py``.  Cases run under the ``xla`` backend and, where
the entry point has a Pallas path, under Pallas interpret mode.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R

# partitions that share one shape bucket, as the frame layer groups them
GROUPS = {
    "bucket_512": (1, 200, ops.PAD_MIN - 1),
    "bucket_1024": (ops.PAD_MIN + 1, 1000),
    "past_tile": (ops.TILE + 1, ops.TILE + 300),
}
LENGTHS = (1, ops.PAD_MIN - 1, ops.PAD_MIN + 1, ops.TILE + 1)
NUM_BUCKETS = 37


def _rng(n, salt):
    return np.random.default_rng([n, salt])


def _groupby_part(n, salt):
    """A frame groupby plan for one partition: keys over 37 buckets (some
    empty at small lengths), sum/min/max rows on two validity rows."""
    rng = _rng(n, salt)
    keys = rng.integers(0, NUM_BUCKETS, n).astype(np.int32)
    values = [rng.normal(size=n).astype(np.float32) for _ in range(3)]
    valids = [rng.uniform(size=n) > 0.25, np.ones(n, bool)]
    return keys, values, valids


MODES, VALID_IDX = ("sum", "min", "max"), (0, 1, 0)


def _stats_stack(n, salt, c=2):
    """(C, pad_len(n)) values and validity, padded as ``_dev_stats_stack``
    pads them."""
    rng = _rng(n, salt)
    nb = ops.pad_len(n)
    xs = np.zeros((c, nb), np.float32)
    ms = np.zeros((c, nb), bool)
    xs[:, :n] = rng.normal(size=(c, n)) * 10
    ms[:, :n] = rng.uniform(size=(c, n)) >= 0.3
    return xs, ms


# --------------------------------------------------------------------- parts --
@pytest.mark.parametrize("group", GROUPS)
def test_segment_reduce_batch_parts(ops_backend, group):
    parts = [_groupby_part(n, 11) for n in GROUPS[group]]
    reds, cnts = ops.segment_reduce_batch_parts(
        [p[0] for p in parts], [p[1] for p in parts], [p[2] for p in parts],
        NUM_BUCKETS, MODES, VALID_IDX,
    )
    for p, (keys, values, valids) in enumerate(parts):
        r1, c1 = ops.segment_reduce_batch(
            keys, values, valids, NUM_BUCKETS, MODES, VALID_IDX
        )
        np.testing.assert_array_equal(np.asarray(reds[p]), np.asarray(r1))
        np.testing.assert_array_equal(np.asarray(cnts[p]), np.asarray(c1))


@pytest.mark.parametrize("group", GROUPS)
def test_topk_padded_parts(ops_backend, group):
    xs = [_rng(n, 12).normal(size=n).astype(np.float32) for n in GROUPS[group]]
    out = np.asarray(ops.topk_padded_parts(xs, 8, largest=False))
    assert out.shape == (len(xs), 8)
    for p, x in enumerate(xs):
        np.testing.assert_array_equal(out[p], np.asarray(ops.topk_padded(x, 8, largest=False)))


@pytest.mark.parametrize("group", GROUPS)
def test_argsort_f64_parts(group):
    """Once per group: every backend shares the jit'd ``lax.sort``."""
    keys = [_rng(n, 13).integers(-50, 50, n) * 0.5 for n in GROUPS[group]]
    out = np.asarray(ops.argsort_f64_parts(keys))
    assert out.shape == (len(keys), ops.pad_len(max(GROUPS[group])))
    for p, k in enumerate(keys):
        np.testing.assert_array_equal(out[p, : len(k)], np.asarray(ops.argsort_f64(k)))
        np.testing.assert_array_equal(out[p, : len(k)], np.argsort(k, kind="stable"))


@pytest.mark.parametrize("group", GROUPS)
def test_filter_compact_padded_parts(ops_backend, group):
    rows = []
    for n in GROUPS[group]:
        rng = _rng(n, 14)
        rows.append((rng.normal(size=n).astype(np.float32), rng.uniform(size=n) < 0.5))
    out, cnts = ops.filter_compact_padded_parts([r[0] for r in rows], [r[1] for r in rows])
    out, cnts = np.asarray(out), np.asarray(cnts)
    for p, (x, keep) in enumerate(rows):
        o1, c1 = ops.filter_compact_padded(x, keep)
        assert int(cnts[p]) == int(c1) == int(keep.sum())
        np.testing.assert_array_equal(
            out[p, : len(x)].view(np.uint32), np.asarray(o1).view(np.uint32)
        )


@pytest.mark.parametrize("group", GROUPS)
def test_masked_stats_batch_parts(ops_backend, group):
    stacks = [_stats_stack(n, 15) for n in GROUPS[group]]
    out = np.asarray(ops.masked_stats_batch_parts([s[0] for s in stacks], [s[1] for s in stacks]))
    assert out.shape == (2 * len(stacks), 5)
    for p, (xs, ms) in enumerate(stacks):
        np.testing.assert_array_equal(
            out[2 * p: 2 * p + 2], np.asarray(ops.masked_stats_batch(xs, ms))
        )


# ---------------------------------------------------------------- composites --
@pytest.mark.parametrize("n", LENGTHS)
def test_filter_then_masked_stats(ops_backend, n):
    """The keep mask covers the partition's ``n`` rows of a padded stack."""
    xs, ms = _stats_stack(n, 16)
    keep = _rng(n, 17).uniform(size=n) < 0.5
    fused = np.asarray(ops.filter_then_masked_stats(xs, ms, keep))
    unfused = np.asarray(ops.masked_stats_batch(xs[:, :n][:, keep], ms[:, :n][:, keep]))
    np.testing.assert_array_equal(fused, unfused)
    for i in range(len(xs)):
        want = R.masked_stats_ref(jnp.asarray(xs[i, :n]), jnp.asarray(ms[i, :n] & keep))
        np.testing.assert_allclose(fused[i], np.asarray(want), rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("n", LENGTHS)
def test_filter_then_segment_reduce(ops_backend, n):
    keys, values, valids = _groupby_part(n, 18)
    keep = _rng(n, 19).uniform(size=n) < 0.5
    reds, cnts = (np.asarray(a) for a in ops.filter_then_segment_reduce(
        keys, values, valids, keep, NUM_BUCKETS, MODES, VALID_IDX
    ))
    r1, c1 = ops.segment_reduce_batch(
        keys[keep], [v[keep] for v in values], [m[keep] for m in valids],
        NUM_BUCKETS, MODES, VALID_IDX,
    )
    np.testing.assert_array_equal(reds, np.asarray(r1))
    np.testing.assert_array_equal(cnts, np.asarray(c1))
    for s, (mode, vi) in enumerate(zip(MODES, VALID_IDX)):
        want, _ = R.segment_reduce_ref(
            jnp.asarray(keys[keep]), jnp.asarray(values[s][keep]),
            jnp.asarray(valids[vi][keep]), NUM_BUCKETS, mode,
        )
        np.testing.assert_allclose(reds[s], np.asarray(want), atol=1e-4)
    for v, m in enumerate(valids):
        np.testing.assert_array_equal(
            cnts[v], np.bincount(keys[keep & m], minlength=NUM_BUCKETS)
        )
