"""Loop-exact cost probes for the roofline (§Roofline methodology).

XLA's HloCostAnalysis counts while-loop bodies ONCE (verified in
tests/test_roofline.py), so the full dry-run compile under-reports flops /
bytes / collective-bytes of the layer-scan by ~n_layers×.  The roofline
therefore sums three *separately compiled, loop-free* artifacts:

    total = outer (embed+logits+loss[+grad], n_layers=0 config)
          + Σ_block-type  n_blocks × block (grad-of-rematted-block, train)
          + optimizer update over the full parameter pytree (train)

Every number still comes from ``compiled.cost_analysis()`` — no analytic
flop counting.  Probes force the unrolled xla attention path (same math as
the scanned one, tested identical).
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, RunConfig
from ..kernels import ops as kops
from ..models.base import ShardCtx, tree_specs_to_shapes
from ..models.blocks import block_fwd, block_spec, init_block_cache
from ..models.lm import forward, lm_loss
from ..train.optimizer import AdamWConfig, adamw_update, init_opt_state
from .roofline import collective_bytes_from_hlo, cost_analysis_dict
from .specs import make_cache_specs, train_input_specs, decode_input_specs


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: Dict[str, int] = dataclasses.field(default_factory=dict)

    def __add__(self, o: "Cost") -> "Cost":
        c = Counter(self.coll)
        c.update(o.coll)
        return Cost(self.flops + o.flops, self.bytes + o.bytes, dict(c))

    def __mul__(self, k: float) -> "Cost":
        return Cost(
            self.flops * k, self.bytes * k,
            {n: int(v * k) for n, v in self.coll.items()},
        )

    __rmul__ = __mul__


def _cost_of(compiled) -> Cost:
    ca = cost_analysis_dict(compiled)
    return Cost(
        flops=float(ca.get("flops", 0.0)),
        bytes=float(ca.get("bytes accessed", 0.0)),
        coll=collective_bytes_from_hlo(compiled.as_text()),
    )


def _named(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def block_counts(cfg: ModelConfig) -> Dict[str, int]:
    counts: Dict[str, int] = Counter()
    for i in range(cfg.n_layers):
        counts[cfg.block_pattern[i % len(cfg.block_pattern)]] += 1
    return dict(counts)


def probe_block(
    cfg: ModelConfig,
    btype: str,
    ctx: ShardCtx,
    mesh,
    B: int,
    S: int,
    kind: str,  # "train" | "prefill" | "decode"
    remat: bool = True,
    ctx_params: ShardCtx = None,
) -> Cost:
    """Compile one block (grad for train; fwd for serving) loop-free.

    ``S``: sequence length (train/prefill) or cache capacity (decode, where
    the block input is a single new token)."""
    spec = block_spec(btype, cfg, ctx_params or ctx)
    p_shapes, p_specs = tree_specs_to_shapes(spec)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x_S = 1 if kind == "decode" else S
    seq_sp = x_S > 1 and x_S % ctx.tp == 0
    x_spec = P(
        ctx.data_spec() if B % ctx.dp_total == 0 else None,
        ctx.model_axis if seq_sp else None,
        None,
    )
    x_shape = jax.ShapeDtypeStruct((B, x_S, cfg.d_model), dt)
    positions = jax.ShapeDtypeStruct((B, x_S), jnp.int32)
    pos_spec = P(x_spec[0], None)

    kops.set_xla_unroll(True)
    try:
        with jax.set_mesh(mesh):
            if kind == "train":

                def fn(x, params, pos):
                    def body(x, params):
                        y, _, aux = block_fwd(
                            btype, params, cfg, x, pos, ctx,
                            use_ep=True, mesh=mesh,
                        )
                        return y, aux

                    if remat:
                        body = jax.checkpoint(body, prevent_cse=False)
                    y, aux = body(x, params)
                    return jnp.sum(y.astype(jnp.float32)) + sum(
                        aux.values(), 0.0
                    )

                probe = jax.grad(fn, argnums=(0, 1))
                jitted = jax.jit(
                    probe,
                    in_shardings=(
                        NamedSharding(mesh, x_spec),
                        _named(mesh, p_specs),
                        NamedSharding(mesh, pos_spec),
                    ),
                )
                compiled = jitted.lower(x_shape, p_shapes, positions).compile()
            else:
                cache = None
                cache_specs = None
                if kind == "decode":
                    cache = jax.eval_shape(
                        lambda: init_block_cache(btype, cfg, B, S)
                    )
                    cache_specs = make_cache_specs(
                        cfg, ctx, {"extra": {f"x0_{btype}": cache}},
                        batch_shardable=(B % ctx.dp_total == 0),
                    )["extra"][f"x0_{btype}"]

                def fn(x, params, pos, c):
                    y, new_c, _ = block_fwd(
                        btype, params, cfg, x, pos, ctx, cache=c,
                        use_ep=True, mesh=mesh,
                    )
                    return y, new_c

                in_sh = [
                    NamedSharding(mesh, x_spec),
                    _named(mesh, p_specs),
                    NamedSharding(mesh, pos_spec),
                ]
                in_sh.append(_named(mesh, cache_specs) if cache is not None else None)
                jitted = jax.jit(fn, in_shardings=tuple(in_sh))
                compiled = jitted.lower(
                    x_shape, p_shapes, positions, cache
                ).compile()
    finally:
        kops.set_xla_unroll(False)
    return _cost_of(compiled)


def probe_outer(
    cfg: ModelConfig, run: RunConfig, ctx: ShardCtx, mesh, kind: str,
    batch_override: int = 0, ctx_params: ShardCtx = None,
) -> Cost:
    """Embed + logits + loss (+ grad wrt embed) with zero layers."""
    cfg0 = dataclasses.replace(cfg, n_layers=0)
    from ..models.lm import model_spec

    spec = model_spec(cfg0, ctx_params or ctx)
    p_shapes, p_specs = tree_specs_to_shapes(spec)
    shape = run.shape
    if batch_override and batch_override != shape.global_batch:
        shape = dataclasses.replace(shape, global_batch=batch_override)
        run = dataclasses.replace(run, shape=shape)
    kops.set_xla_unroll(True)
    try:
        with jax.set_mesh(mesh):
            if kind == "train":
                in_shapes, in_specs = train_input_specs(cfg0, shape, ctx)

                def fn(params, batch):
                    logits, _, _ = forward(
                        params, cfg0, batch["tokens"], ctx, mesh=mesh,
                        vis_embeds=batch.get("vis_embeds"),
                    )
                    return lm_loss(logits, batch["labels"], cfg0.vocab)

                jitted = jax.jit(
                    jax.grad(fn),
                    in_shardings=(_named(mesh, p_specs), _named(mesh, in_specs)),
                )
                compiled = jitted.lower(p_shapes, in_shapes).compile()
            elif kind == "prefill":
                in_shapes, in_specs = train_input_specs(cfg0, shape, ctx)

                def fn(params, batch):
                    logits, _, _ = forward(
                        params, cfg0, batch["tokens"], ctx, mesh=mesh,
                        vis_embeds=batch.get("vis_embeds"),
                    )
                    return logits[:, -1]

                jitted = jax.jit(
                    fn,
                    in_shardings=(_named(mesh, p_specs), _named(mesh, in_specs)),
                )
                compiled = jitted.lower(p_shapes, in_shapes).compile()
            else:
                in_shapes, in_specs = decode_input_specs(cfg0, shape, ctx)

                def fn(params, tokens, pos):
                    logits, _, _ = forward(
                        params, cfg0, tokens, ctx, mesh=mesh, cache={},
                        start_pos=pos,
                    )
                    return logits[:, -1]

                jitted = jax.jit(
                    fn,
                    in_shardings=(
                        _named(mesh, p_specs),
                        _named(mesh, in_specs["tokens"]),
                        NamedSharding(mesh, P()),
                    ),
                )
                compiled = jitted.lower(
                    p_shapes, in_shapes["tokens"], in_shapes["pos"]
                ).compile()
    finally:
        kops.set_xla_unroll(False)
    return _cost_of(compiled)


def probe_optimizer(
    cfg: ModelConfig, run: RunConfig, ctx: ShardCtx, mesh
) -> Cost:
    from ..train.trainstep import train_state_specs

    (p_shapes, p_specs), (o_shapes, o_specs) = train_state_specs(cfg, run, ctx)
    o_shapes = {k: v for k, v in o_shapes.items() if k != "err"}
    o_specs = {k: v for k, v in o_specs.items() if k != "err"}
    g_shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p_shapes
    )
    opt = AdamWConfig(lr=run.lr, weight_decay=run.weight_decay,
                      grad_clip=run.grad_clip)

    def fn(params, grads, state):
        return adamw_update(opt, params, grads, state)

    with jax.set_mesh(mesh):
        jitted = jax.jit(
            fn,
            in_shardings=(
                _named(mesh, p_specs),
                _named(mesh, p_specs),
                _named(mesh, o_specs),
            ),
        )
        compiled = jitted.lower(p_shapes, g_shapes, o_shapes).compile()
    return _cost_of(compiled)


def corrected_costs(
    cfg: ModelConfig, run: RunConfig, ctx: ShardCtx, mesh, kind: str,
    ctx_params: ShardCtx = None,
) -> Tuple[Cost, Dict[str, Any]]:
    """The loop-exact per-device cost of one full step.

    ``ctx_params``: param-sharding context (decode cells shard params over
    the model axis only).  Microbatched train steps probe one microbatch and
    scale by the number of micro-steps (the optimizer runs once)."""
    ctx_params = ctx_params or ctx
    shape = run.shape
    B = shape.global_batch
    n_micro = 1
    if kind == "train" and run.microbatch:
        n_micro = max(1, B // run.microbatch)
        B = run.microbatch
    S = shape.seq_len if kind != "decode" else 1
    total = probe_outer(
        cfg, run, ctx, mesh, kind, batch_override=B, ctx_params=ctx_params
    )
    detail: Dict[str, Any] = {"outer_flops": total.flops}
    for btype, n in block_counts(cfg).items():
        if kind == "decode":
            c = probe_block(
                cfg, btype, ctx, mesh, shape.global_batch, shape.seq_len,
                "decode", ctx_params=ctx_params,
            )
        else:
            c = probe_block(
                cfg, btype, ctx, mesh, B, S, kind,
                remat=(run.remat != "none"),
            )
        detail[f"block_{btype}_flops"] = c.flops
        total = total + n * c
    if n_micro > 1:
        total = total * n_micro
    if kind == "train":
        c = probe_optimizer(cfg, run, ctx, mesh)
        detail["opt_flops"] = c.flops
        total = total + c
    return total, detail
