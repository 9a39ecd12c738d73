"""Training loop, checkpointing, fault tolerance, data, serving tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.configs import RunConfig, get_shape, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.data import PrefetchLoader, SynthSpec, batch_at, make_iterator
from repro.models import ShardCtx, init_model
from repro.serve import OpportunisticServer, make_serve_fns
from repro.train import AdamWConfig, train_loop
from repro.train.optimizer import (
    adamw_update,
    clip_by_global_norm,
    dequantize_int8,
    init_opt_state,
    quantize_int8,
)

SMALL_SHAPE = ShapeConfig("tiny", "train", seq_len=32, global_batch=4)


def _runcfg(cfg, **kw):
    return RunConfig(model=cfg, shape=SMALL_SHAPE, dp=1, tp=1, remat="none", **kw)


def test_synth_data_deterministic_and_structured():
    spec = SynthSpec(vocab=64, seq_len=32, batch=4, seed=3)
    b1 = batch_at(spec, step=5)
    b2 = batch_at(spec, step=5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = batch_at(spec, step=6)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])
    # structure: the bigram rule fires most of the time
    det = (b1["tokens"] * 31 + 7) % 64
    agree = (b1["labels"] == det).mean()
    assert agree > 0.5


def test_prefetch_loader():
    spec = SynthSpec(vocab=64, seq_len=16, batch=2)
    loader = PrefetchLoader(make_iterator(spec), depth=2)
    batches = [next(loader) for _ in range(3)]
    ref = [batch_at(spec, i) for i in range(3)]
    for b, r in zip(batches, ref):
        np.testing.assert_array_equal(b["tokens"], r["tokens"])
    loader.close()


def test_adamw_reduces_loss_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = init_opt_state(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}  # d/dw ||w||²
        params, state, m = adamw_update(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_grad_clip():
    grads = {"a": jnp.full((4,), 100.0)}
    clipped, gnorm = clip_by_global_norm(grads, 1.0)
    assert float(gnorm) == pytest.approx(200.0)
    total = jnp.sqrt(sum(jnp.sum(g**2) for g in jax.tree.leaves(clipped)))
    assert float(total) == pytest.approx(1.0, rel=1e-5)


def test_int8_error_feedback_unbiased():
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=256), jnp.float32)
    err = jnp.zeros(256)
    acc = jnp.zeros(256)
    for _ in range(64):  # same gradient repeatedly: EF must recover it
        g_ef = g_true + err
        q, s = quantize_int8(g_ef)
        deq = dequantize_int8(q, s)
        err = g_ef - deq
        acc = acc + deq
    np.testing.assert_allclose(np.asarray(acc / 64), np.asarray(g_true), atol=1e-3)


def test_train_loss_decreases():
    cfg = get_smoke_config("smollm_360m")
    run = _runcfg(cfg)
    data = SynthSpec(vocab=cfg.vocab, seq_len=32, batch=4, seed=0)
    stats = train_loop(
        cfg, run, data, total_steps=30,
        opt=AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=30),
        log_every=1000, log_fn=lambda s: None,
    )
    first = np.mean(stats.losses[:5])
    last = np.mean(stats.losses[-5:])
    assert last < first - 0.2, (first, last)


def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    m.save(10, tree)
    m.save(20, tree)
    m.save(30, tree)
    assert m.latest_step() == 30
    # keep=2: step 10 GC'd
    assert not os.path.exists(tmp_path / "step_00000010")
    out = m.restore(tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    # partial tmp dirs are ignored
    os.makedirs(tmp_path / ".tmp_step_00000099", exist_ok=True)
    assert m.latest_step() == 30


def test_failure_injection_and_resume(tmp_path):
    """Kill the loop mid-run; restarting resumes from the checkpoint."""
    cfg = get_smoke_config("smollm_360m")
    run = _runcfg(cfg)
    data = SynthSpec(vocab=cfg.vocab, seq_len=32, batch=4, seed=0)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    with pytest.raises(RuntimeError, match="injected node failure"):
        train_loop(
            cfg, run, data, total_steps=20, ckpt_dir=str(tmp_path),
            ckpt_every=5, opt=opt, fail_at_step=12, log_fn=lambda s: None,
        )
    m = CheckpointManager(str(tmp_path))
    assert m.latest_step() is not None and m.latest_step() >= 10
    stats = train_loop(
        cfg, run, data, total_steps=20, ckpt_dir=str(tmp_path),
        ckpt_every=5, opt=opt, log_fn=lambda s: None,
    )
    assert stats.resumed_from is not None and stats.resumed_from >= 10
    assert stats.steps == 20 - stats.resumed_from


def test_elastic_restore_resharding(tmp_path):
    """A checkpoint restores under a different device placement."""
    m = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    m.save(1, tree)
    # restore with an explicit (trivial single-device) sharding fn
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh(
        (1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    out = m.restore(tree, sharding_fn=lambda key: NamedSharding(mesh, P()))
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


def test_serve_prefill_decode_consistency():
    cfg = get_smoke_config("qwen3_8b")
    ctx = ShardCtx()
    params = init_model(cfg, ctx, seed=0)
    prefill, decode, _ = make_serve_fns(cfg, ctx, capacity=64)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    logits, cache = prefill(params, prompt)
    # prefill last-token logits == full forward last-token logits
    from repro.models import forward

    ref, _, _ = forward(params, cfg, prompt, ctx)
    np.testing.assert_allclose(
        np.asarray(logits, np.float32), np.asarray(ref[:, -1], np.float32),
        atol=0.1, rtol=0.1,
    )
    # a decode step extends consistently
    nxt = jnp.argmax(logits[..., : cfg.vocab], -1).astype(jnp.int32)[:, None]
    lg2, cache = decode(params, cache, nxt, jnp.asarray(16, jnp.int32))
    assert lg2.shape == logits.shape


def test_opportunistic_server_speculative_prefill():
    cfg = get_smoke_config("smollm_360m")
    params = init_model(cfg, ShardCtx(), seed=0)
    srv = OpportunisticServer(cfg, params, step_cost_s=0.05, prefill_cost_s=0.1)
    prompt = tuple(range(1, 33))

    # cold request pays prefill + decode
    srv.request(prompt, n_tokens=4)
    cold = srv.metrics.interactions[-1].latency_s

    # anticipate a prompt; think time warms its prefix cache
    nxt = tuple(range(2, 34))
    srv.anticipate(nxt)
    srv.think(10.0)
    srv.request(nxt, n_tokens=4)
    warm = srv.metrics.interactions[-1].latency_s
    assert warm < cold  # speculative prefill removed the prefill latency
    # identical resubmission is pure cache hit (CSE + materialised cache)
    srv.request(nxt, n_tokens=4)
    again = srv.metrics.interactions[-1].latency_s
    assert again <= warm
