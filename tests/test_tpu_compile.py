"""Compile rehearsal: the frame kernels and the sharded combine lower for v5e.

Interpret mode cannot see what the TPU compiler refuses (block shapes off the
(8, 128) tiling, unsupported in-kernel ops, f64 collectives).  Each test
compiles one entry point for a *described* ``v5e:2x2`` — no chip attached —
at the partition bucket the chip smoke's table produces, and checks that the
Mosaic kernel (``tpu_custom_call``) or the expected collectives are in the
compiled program.  Nothing runs, so nothing here says anything about results
or times.
"""
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.frame import dist
from repro.kernels import filter_compact, masked_stats, ops, segment_reduce, topk
from repro.kernels.join_probe import merge_probe

ROOT = Path(__file__).resolve().parents[1]
DIM_ROWS = 1 << 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bucket(smoke):
    """The largest shape bucket of the smoke table's partitions."""
    return max(smoke.partition_buckets())


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo, no_compile_cache):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


KERNELS = {
    "masked_stats": lambda n, S: (
        masked_stats,
        (S((n,), jnp.float32), S((n,), jnp.bool_)),
    ),
    "segment_reduce": lambda n, S: (
        lambda k, v, m: segment_reduce(k, v, m, 128, mode="sum"),
        (S((n,), jnp.int32), S((n,), jnp.float32), S((n,), jnp.bool_)),
    ),
    "filter_compact": lambda n, S: (
        filter_compact,
        (S((n,), jnp.float32), S((n,), jnp.bool_)),
    ),
    "topk": lambda n, S: (
        lambda x: topk(x, 10),
        (S((n,), jnp.float32),),
    ),
    "join_probe": lambda n, S: (
        merge_probe,
        (S((n,), jnp.float32), S((DIM_ROWS,), jnp.float32)),
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_frame_kernel_lowers_for_v5e(name, chip, bucket):
    fn, args = KERNELS[name](bucket, chip)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("n", [1024, "bucket"])
def test_filter_compact_stitches_in_the_kernel_for_v5e(n, chip, bucket):
    """One tile (1,024 values) and many (the smoke table's largest bucket):
    the rows are placed by the kernel, with no XLA sort or scatter around
    it."""
    n = bucket if n == "bucket" else n
    text = _compiled_text(filter_compact, chip((n,), jnp.float32), chip((n,), jnp.bool_))
    assert "tpu_custom_call" in text
    assert " sort(" not in text and " scatter(" not in text


# the star's dimensions: TPC-H's orders and part at scale factor 1
ORDERS_ROWS, PART_ROWS = 1_500_000, 200_000


@pytest.mark.parametrize("n", [1 << 15, 1 << 17, 1 << 21])
def test_join_probe_padded_sorts_nothing_for_v5e(n, chip):
    """The probe's entry point at the star cell's left buckets (a month of
    lines, a quarter, a whole partition) against orders: the Pallas merge
    and no XLA sort, whose compile takes the TPU compiler tens of seconds
    at these lengths (the host puts the keys in band order)."""
    with ops.local_backend("pallas"):
        text = _compiled_text(ops.join_probe_padded,
                              chip((ORDERS_ROWS,), jnp.float32), chip((n,), jnp.float32))
    assert "tpu_custom_call" in text and " sort(" not in text


@pytest.mark.parametrize("m", [ORDERS_ROWS, PART_ROWS])
@pytest.mark.parametrize("n", [1 << 15, 1 << 17, 1 << 21])
def test_join_probe_merge_compiles_for_v5e(n, m, chip):
    """The merge kernel against a 1.5M-row orders and a 200k-row part
    dimension: the Pallas call, and no sort of the right side."""
    text = _compiled_text(merge_probe,
                          chip((n,), jnp.float32), chip((m,), jnp.float32))
    assert "tpu_custom_call" in text and " sort(" not in text


# the backend-dispatching entry points that wrap the kernels on the pallas
# backend: several kernel calls in one traced program
ENTRY_POINTS = {
    "masked_stats_batch": lambda n, S: (
        ops.masked_stats_batch,
        (S((3, n), jnp.float32), S((3, n), jnp.bool_)),
    ),
    "segment_reduce_batch": lambda n, S: (
        lambda k, v, m: ops.segment_reduce_batch(
            k, v, m, 64, ("sum", "sum"), (0, 1)
        ),
        (S((n,), jnp.int32), (S((n,), jnp.float32),) * 2, (S((n,), jnp.bool_),) * 2),
    ),
    "join_probe_padded": lambda n, S: (
        ops.join_probe_padded,
        (S((DIM_ROWS,), jnp.float32), S((n,), jnp.float32)),
    ),
}


@pytest.mark.parametrize("name", sorted(ENTRY_POINTS))
def test_pallas_entry_point_lowers_for_v5e(name, chip, bucket):
    fn, args = ENTRY_POINTS[name](bucket, chip)
    with ops.local_backend("pallas"):
        text = _compiled_text(fn, *args)
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def data_mesh4(topo, no_compile_cache, smoke):
    """The smoke table's partitions laid out over a described 4-chip ``data``
    mesh: (spec builder, Ppad, pl, d, nb)."""
    mesh = Mesh(np.array(topo.devices), (dist.AXIS,))
    sharding = NamedSharding(mesh, PartitionSpec(dist.AXIS))
    rows = smoke.partition_rows()
    ppad, pl, d = dist._padded_layout(len(rows), mesh)
    nb = dist._common_bucket(rows)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return mesh, spec, ppad, pl, d, nb


def test_sharded_stats_raws_lower_on_four_chips(data_mesh4):
    """The describe/mean dispatch over the 4-chip mesh: per-partition raws,
    each device computing its own block of partitions; the merge runs on the
    host, so the program holds no f64."""
    mesh, spec, ppad, pl, d, nb = data_mesh4
    C = 3
    fn = dist._make_stats_raws(mesh, pl, C, nb)
    text = fn.lower(
        spec((ppad, C, nb), jnp.float32), spec((ppad, C, nb), jnp.bool_),
    ).compile().as_text()
    assert d == 4
    assert "num_partitions=4" in text
    assert f"f32[{pl},{C},{nb}]" in text and "f64" not in text


@pytest.mark.parametrize("op", ["groupby", "value_counts"])
def test_sharded_fold_lowers_on_four_chips(op, data_mesh4):
    """The groupby / value_counts combine over the 4-chip ``data`` mesh folds
    in f64 inside the jit; the TPU must accept it at the smoke's layout.  Its
    f64 collectives lower as all-gathers (the TPU emulates f64)."""
    mesh, spec, ppad, pl, d, nb = data_mesh4
    S, modes = (1, ("sum",)) if op == "groupby" else (0, ())

    with jax.enable_x64(True):
        fn = dist._make_segment_fold(mesh, pl, d, nb, 64, S, 1, modes, (0,) * S)
        text = fn.lower(
            spec((ppad, nb), jnp.int32),
            spec((ppad, S, nb), jnp.float32),
            spec((ppad, 1, nb), jnp.bool_),
        ).compile().as_text()
    assert d == 4
    assert "all-gather" in text and "f64" in text
