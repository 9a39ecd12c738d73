"""Mixture-of-Experts FFN with TPU-native expert parallelism.

Hardware adaptation (DESIGN.md §5): GPU MoE implementations all-to-all tokens
to expert-owning devices.  Under tensor parallelism the activations are
already replicated across the ``model`` axis, so experts sharded over that
axis need **zero dispatch traffic**: every model shard routes its local tokens
to its local expert slice and the combine rides the psum the TP FFN output
already requires.  Dispatch inside a shard is sort-based capacity grouping →
grouped GEMM (static shapes, MXU-friendly, MegaBlocks-flavoured), not scatter.

Two equivalent paths:
  * `moe_ffn`     — global semantics (single device / smoke tests / oracle)
  * `moe_ffn_ep`  — the shard_map expert-parallel body (called with local
                    expert slices + a psum over the model axis)
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .base import ParamSpec, ShardCtx, matrix_spec, replicated_spec


def moe_spec(cfg: ModelConfig, ctx: ShardCtx) -> Dict[str, ParamSpec]:
    assert cfg.moe is not None
    d = cfg.d_model
    e_pad = cfg.moe.padded_experts(ctx.tp)
    f = cfg.moe.d_ff_expert
    specs = {
        "router": matrix_spec(ctx, (d, e_pad), tp_dim=None, fsdp_dim=0,
                              init="normal:0.01"),
        "w_up": matrix_spec(ctx, (e_pad, d, f), tp_dim=0, fsdp_dim=1),
        "w_down": matrix_spec(ctx, (e_pad, f, d), tp_dim=0, fsdp_dim=2),
    }
    if cfg.mlp_type in ("swiglu", "geglu"):
        specs["w_gate"] = matrix_spec(ctx, (e_pad, d, f), tp_dim=0, fsdp_dim=1)
    return specs


def _route(params, cfg: ModelConfig, xf: jnp.ndarray, e_pad: int):
    """Router: top-k over real experts (padded experts masked to -inf)."""
    moe = cfg.moe
    logits = (xf @ params["router"].astype(xf.dtype)).astype(jnp.float32)
    if e_pad > moe.n_experts:
        pad_mask = jnp.arange(e_pad) >= moe.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, moe.top_k)  # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    # aux losses (Switch-style load balance + router z-loss)
    T = xf.shape[0]
    frac_tokens = jnp.zeros(e_pad).at[top_e.reshape(-1)].add(1.0) / (T * moe.top_k)
    mean_probs = jnp.mean(probs, axis=0)
    aux = moe.n_experts * jnp.sum(frac_tokens * mean_probs)
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return top_w, top_e, {"moe_aux": aux * moe.aux_loss_coef,
                          "moe_z": zloss * moe.router_z_coef}


def _group_and_compute(
    params, cfg: ModelConfig, xf, top_w, top_e, e_first, e_count: int,
    capacity: int, slice_start=None,
):
    """Sort-based capacity grouping + grouped GEMM over experts
    [e_first, e_first + e_count); returns the weighted combine (T, d).

    ``slice_start``: where those experts live inside ``params`` (0 when the
    params are already local slices under shard_map; defaults to e_first)."""
    if slice_start is None:
        slice_start = e_first
    moe = cfg.moe
    T, d = xf.shape
    k = moe.top_k
    flat_e = top_e.reshape(-1)  # (T*k,)
    flat_w = top_w.reshape(-1)
    local_e = flat_e - e_first
    in_range = (local_e >= 0) & (local_e < e_count)
    sort_key = jnp.where(in_range, local_e, e_count)  # out-of-range sorts last
    order = jnp.argsort(sort_key)  # (T*k,) stable
    sorted_e = sort_key[order]
    # position within each expert's run (first-occurrence via searchsorted)
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_e = jnp.arange(sorted_e.shape[0]) - first
    keep = (sorted_e < e_count) & (pos_in_e < capacity)
    slot = jnp.where(keep, sorted_e * capacity + pos_in_e, e_count * capacity)
    token_of = order // k

    # gather tokens into the (E_loc, C, d) grid
    buf = jnp.zeros((e_count * capacity + 1, d), xf.dtype)
    buf = buf.at[slot].set(xf[token_of], mode="drop")
    grid = buf[:-1].reshape(e_count, capacity, d)

    dt = xf.dtype
    w_up = jax.lax.dynamic_slice_in_dim(params["w_up"], slice_start, e_count, 0)
    w_down = jax.lax.dynamic_slice_in_dim(params["w_down"], slice_start, e_count, 0)
    if cfg.mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
        w_gate = jax.lax.dynamic_slice_in_dim(params["w_gate"], slice_start, e_count, 0)
        g = jnp.einsum("ecd,edf->ecf", grid, w_gate.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", grid, w_up.astype(dt))
        h = act(g.astype(jnp.float32)).astype(dt) * u
    else:
        u = jnp.einsum("ecd,edf->ecf", grid, w_up.astype(dt))
        h = jax.nn.gelu(u.astype(jnp.float32)).astype(dt)
    y_grid = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))  # (E,C,d)

    # weighted scatter-combine back to tokens
    y_flat = y_grid.reshape(e_count * capacity, d)
    y_assign = jnp.where(
        keep[:, None], y_flat[jnp.minimum(slot, e_count * capacity - 1)], 0.0
    )
    w_assign = flat_w[order][:, None].astype(dt)
    out = jnp.zeros((T, d), dt).at[token_of].add(y_assign * w_assign)
    return out


def expert_capacity(cfg: ModelConfig, tokens: int) -> int:
    moe = cfg.moe
    raw = tokens * moe.top_k / moe.n_experts * moe.capacity_factor
    return max(8, int(math.ceil(raw / 8.0)) * 8)


def moe_ffn(params, cfg: ModelConfig, x: jnp.ndarray, ctx: ShardCtx
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Global-semantics MoE FFN: x (B,S,d) → (y, aux_losses)."""
    B, S, d = x.shape
    e_pad = cfg.moe.padded_experts(ctx.tp)
    xf = x.reshape(B * S, d)
    top_w, top_e, aux = _route(params, cfg, xf, e_pad)
    cap = expert_capacity(cfg, B * S)
    y = _group_and_compute(params, cfg, xf, top_w, top_e, 0, e_pad, cap)
    return y.reshape(B, S, d), aux


def moe_ffn_sharded(
    params, cfg: ModelConfig, x: jnp.ndarray, ctx: ShardCtx, mesh
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Expert-parallel MoE: shard_map island inside the pjit program.

    Experts live sliced over the model axis (EP); activations enter batch-
    sharded over the data axes and replicated over model (shard_map reshards
    from the SP-sharded residual stream automatically); the combine is one
    psum over model — the same collective the dense TP FFN would need."""
    from jax.sharding import PartitionSpec as P

    dspec = ctx.data_spec() if x.shape[0] % ctx.dp_total == 0 else None
    x_spec = P(dspec, None, None)
    param_specs = {
        "router": P(None, None),
        "w_up": P(ctx.model_axis, None, None),
        "w_down": P(ctx.model_axis, None, None),
    }
    if "w_gate" in params:
        param_specs["w_gate"] = P(ctx.model_axis, None, None)
    aux_spec = {"moe_aux": P(), "moe_z": P()}

    def body(p, xl):
        return moe_ffn_ep(p, cfg, xl, ctx)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=(x_spec, aux_spec),
    )(params, x)


def moe_ffn_ep(
    params_local, cfg: ModelConfig, x_local: jnp.ndarray, ctx: ShardCtx
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """shard_map body: params_local hold E_pad/tp experts; x_local is this
    data-shard's tokens (replicated across the model axis).  Combine = the
    TP psum the dense FFN needs anyway — zero extra dispatch collectives."""
    B, S, d = x_local.shape
    e_pad = cfg.moe.padded_experts(ctx.tp)
    e_loc = e_pad // ctx.tp
    tp_idx = jax.lax.axis_index(ctx.model_axis)
    xf = x_local.reshape(B * S, d)
    top_w, top_e, aux = _route(params_local, cfg, xf, e_pad)
    cap = expert_capacity(cfg, B * S)
    y = _group_and_compute(
        params_local, cfg, xf, top_w, top_e, tp_idx * e_loc, e_loc, cap,
        slice_start=0,
    )
    y = jax.lax.psum(y, ctx.model_axis)
    # aux scalars: inputs are replicated over the model axis (so aux is too);
    # mean over the data axes makes them fully replicated (out_spec P()) and
    # equal to the global-batch average the loss wants.
    aux = {k: jax.lax.pmean(v, tuple(ctx.data_axes)) for k, v in aux.items()}
    return y.reshape(B, S, d), aux
