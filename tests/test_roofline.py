"""Roofline machinery: HLO collective parsing, cost-analysis semantics,
probe corrections, dry-run smoke (tiny mesh)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.roofline import (
    RooflineReport,
    collective_bytes_from_hlo,
    cost_analysis_dict,
    model_flops_for,
)


def test_collective_parser_on_real_hlo():
    hlo = textwrap.dedent(
        """
        ROOT %all-reduce = f32[32,128]{1,0} all-reduce(%dot.1), channel_id=1
        %ag = bf16[4,256]{1,0} all-gather(%p0), dimensions={1}
        %ag2.done = bf16[4,256]{1,0} all-gather-done(%ag2s)
        %ag2s = bf16[4,256]{1,0} all-gather-start(%p1)
        %cp = f32[8]{0} collective-permute(%x), source_target_pairs={{0,1}}
        %unrelated = f32[2]{0} add(%a, %b)
        """
    )
    out = collective_bytes_from_hlo(hlo)
    assert out["all-reduce"] == 32 * 128 * 4
    assert out["all-gather"] == 4 * 256 * 2 * 2  # plain + start (done skipped)
    assert out["collective-permute"] == 8 * 4


def test_cost_analysis_is_per_device():
    """The roofline's core assumption (DESIGN.md §6), checked empirically."""
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (run under dryrun env)")
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh(
        (jax.device_count(),), ("data",),
        axis_types=(jax.sharding.AxisType.Auto,),
    )
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    with jax.set_mesh(mesh):
        c = (
            jax.jit(
                lambda x, w: x @ w,
                in_shardings=(NamedSharding(mesh, P("data", None)),
                              NamedSharding(mesh, P())),
            )
            .lower(x, w)
            .compile()
        )
    full = 2 * 64 * 128 * 64
    assert cost_analysis_dict(c)["flops"] == pytest.approx(
        full / jax.device_count()
    )


def test_scan_bodies_counted_once():
    """The motivation for launch/probe.py."""
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)

    def scanned(x, w):
        out, _ = jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=10)
        return out

    def unrolled(x, w):
        for _ in range(10):
            x = x @ w
        return x

    f_scan = cost_analysis_dict(jax.jit(scanned).lower(x, w).compile())["flops"]
    f_unroll = cost_analysis_dict(jax.jit(unrolled).lower(x, w).compile())["flops"]
    assert f_unroll == pytest.approx(10 * (f_scan - 2), rel=0.05)


def test_roofline_report_terms():
    r = RooflineReport(
        arch="a", shape="train_4k", mesh="16x16", chips=256,
        flops_per_device=197e12,  # exactly 1s of compute
        bytes_per_device=819e9,  # exactly 1s of HBM
        collective_bytes_per_device=150e9,  # exactly 1s of ICI (3 links)
        collective_by_kind={}, peak_memory_per_device=8 * 2**30,
        model_flops=197e12 * 256 * 0.5,
    )
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(1.0)
    assert r.t_collective == pytest.approx(1.0)
    assert r.useful_flops_fraction == pytest.approx(0.5)
    assert r.roofline_fraction == pytest.approx(0.5)


def test_model_flops_decode_vs_train():
    from repro.configs import get_config, get_shape

    cfg = get_config("qwen3_8b")
    train = model_flops_for(cfg, get_shape("train_4k"))
    decode = model_flops_for(cfg, get_shape("decode_32k"))
    n = cfg.param_count()
    assert train == pytest.approx(6 * n * 4096 * 256)
    assert decode == pytest.approx(2 * n * 128)


def test_dryrun_cell_tiny_mesh_subprocess():
    """dryrun lowers+compiles on a small forced-device-count mesh (the full
    512-device sweep is exercised by results/dryrun_*.jsonl)."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.configs import RunConfig, get_shape, get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.launch.roofline import cost_analysis_dict
        from repro.launch.specs import train_input_specs
        from repro.models.base import ShardCtx, tree_specs_to_shapes
        from repro.train.trainstep import make_train_step, train_state_specs
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_smoke_config("qwen3_8b")
        shape = get_shape("train_4k")
        import dataclasses
        shape = dataclasses.replace(shape, global_batch=4, seq_len=64)
        mesh = make_mesh(dp=2, tp=4)
        ctx = ShardCtx(tp=4, dp=2)
        run = RunConfig(model=cfg, shape=shape, dp=2, tp=4, remat="full")
        (ps, pspec), (os_, ospec) = train_state_specs(cfg, run, ctx)
        ins, ispec = train_input_specs(cfg, shape, ctx)
        named = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        step, _ = make_train_step(cfg, run, mesh=mesh)
        with jax.set_mesh(mesh):
            c = jax.jit(step, in_shardings=(named(pspec), named(ospec),
                                            named(ispec))).lower(
                ps, os_, ins).compile()
        assert cost_analysis_dict(c)["flops"] > 0
        print("TINY_DRYRUN_OK")
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=".",
    )
    assert "TINY_DRYRUN_OK" in out.stdout, out.stderr[-2000:]


def test_distributed_frame_ops_subprocess():
    """shard_map describe/groupby over 8 fake devices match the oracle."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.frame.dist import (
            make_distributed_describe, make_distributed_groupby_sum,
            shard_column)
        mesh = jax.make_mesh(
            (8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(0)
        n, nb = 4096, 16
        x = jnp.asarray(rng.normal(size=n), jnp.float32)
        m = jnp.asarray(rng.uniform(size=n) > 0.25)
        keys = jnp.asarray(rng.integers(0, nb, n), jnp.int32)
        with jax.set_mesh(mesh):
            desc = make_distributed_describe(mesh)
            out = np.asarray(desc(shard_column(mesh, x), shard_column(mesh, m)))
            xs = np.asarray(x)[np.asarray(m)]
            assert abs(out[0] - xs.size) < 1e-3
            assert abs(out[1] - xs.mean()) < 1e-4
            assert abs(out[2] - xs.std(ddof=1)) < 1e-3
            gb = make_distributed_groupby_sum(mesh, nb)
            sums, counts = gb(shard_column(mesh, keys), shard_column(mesh, x),
                              shard_column(mesh, m))
            ref = np.zeros(nb); cnt = np.zeros(nb)
            kk = np.asarray(keys); xx = np.asarray(x); mm = np.asarray(m)
            for k, v, ok in zip(kk, xx, mm):
                if ok:
                    ref[k] += v; cnt[k] += 1
            np.testing.assert_allclose(np.asarray(sums), ref, atol=1e-3)
            np.testing.assert_allclose(np.asarray(counts), cnt)
        print("DIST_FRAME_OK")
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=".",
    )
    assert "DIST_FRAME_OK" in out.stdout, out.stderr[-2000:]
