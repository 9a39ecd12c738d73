"""GQA attention block: projections + RoPE + (qk-norm) + kernel dispatch +
KV caches (full, sliding-window ring buffer).

Compute path: `repro.kernels.ops.attention` — Pallas flash kernel on TPU,
blocked-jnp reference on CPU (identical math).  Decode against a
sequence-sharded cache (split-S / FlashDecoding-style) is provided for the
serving layer via logsumexp-combinable partial attention.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops as kops
from .base import ParamSpec, ShardCtx, matrix_spec, replicated_spec
from .layers import apply_rope, compute_dtype, rms_head_norm, rope_freqs


def attn_spec(cfg: ModelConfig, ctx: ShardCtx) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    qh, kvh, hd = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    tp_ok = cfg.attn_tp_eligible(ctx.tp)
    kv_ok = cfg.kv_sharded(ctx.tp)
    out = {
        "wq": matrix_spec(ctx, (d, qh * hd), tp_dim=1 if tp_ok else None, fsdp_dim=0),
        "wk": matrix_spec(ctx, (d, kvh * hd), tp_dim=1 if kv_ok else None, fsdp_dim=0),
        "wv": matrix_spec(ctx, (d, kvh * hd), tp_dim=1 if kv_ok else None, fsdp_dim=0),
        "wo": matrix_spec(ctx, (qh * hd, d), tp_dim=0 if tp_ok else None, fsdp_dim=1),
    }
    if cfg.qk_norm:
        out["q_norm"] = replicated_spec((hd,), "ones")
        out["k_norm"] = replicated_spec((hd,), "ones")
    return out


@jax.tree_util.register_dataclass
@dataclass
class KVCache:
    """Contiguous cache (full attention) or ring buffer (sliding window)."""

    k: jnp.ndarray  # (B, Hkv, C, D)
    v: jnp.ndarray  # (B, Hkv, C, D)
    pos: jnp.ndarray  # scalar int32: tokens seen so far

    @property
    def capacity(self) -> int:
        return self.k.shape[2]


def init_kv_cache(
    cfg: ModelConfig, batch: int, capacity: int, window: Optional[int] = None
) -> KVCache:
    cap = min(capacity, window) if window else capacity
    dt = compute_dtype(cfg)
    shape = (batch, cfg.n_kv_heads, cap, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt), pos=jnp.zeros((), jnp.int32)
    )


def _project_qkv(params, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    dt = x.dtype
    q = (x @ params["wq"].astype(dt)).reshape(B, S, cfg.n_q_heads, cfg.head_dim)
    k = (x @ params["wk"].astype(dt)).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ params["wv"].astype(dt)).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_head_norm(params["q_norm"], q)
        k = rms_head_norm(params["k_norm"], k)
    q = q.transpose(0, 2, 1, 3)  # (B, H, S, D)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    cos, sin = rope_freqs(cfg, positions)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def attention_block(
    params,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (B, S, d)
    positions: jnp.ndarray,  # (B, S)
    window: Optional[int] = None,
    cache: Optional[KVCache] = None,
    mesh=None,
    ctx=None,
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Full-sequence (train/prefill) or single-step (decode) attention."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)

    use_split_s = (
        cache is not None
        and S == 1
        and mesh is not None
        and ctx is not None
        and ctx.tp > 1
        and (window is None or cache.capacity != window)
        and cache.capacity % ctx.tp == 0
    )
    if cache is not None and use_split_s:
        # FlashDecoding-style split-S: the cache stays sequence-sharded over
        # the model axis; each shard computes partial attention over its
        # slice and the combine is a tiny (o·l, l, m) psum — GSPMD would
        # otherwise all-gather the whole cache every token (measured 2.1 GB
        # per layer on qwen3-8b decode_32k; see EXPERIMENTS.md §Perf).
        slot = cache.pos
        k_new = jax.lax.dynamic_update_slice(
            cache.k, k, (0, 0, slot.astype(jnp.int32), 0)
        )
        v_new = jax.lax.dynamic_update_slice(
            cache.v, v, (0, 0, slot.astype(jnp.int32), 0)
        )
        new_cache = KVCache(k=k_new, v=v_new, pos=cache.pos + S)
        out = _split_s_decode(
            q * (cfg.head_dim ** -0.5), k_new, v_new, cache.pos, mesh, ctx
        ).astype(x.dtype)
        out = out[:, :, None, :]  # (B, Hq, 1, D)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_q_heads * cfg.head_dim)
        return out @ params["wo"].astype(x.dtype), new_cache

    if cache is None:
        out = kops.attention(q, k, v, causal=True, window=window)
        new_cache = None
    else:
        # decode: append to the cache (ring-buffer for windowed attention)
        cap = cache.capacity
        if window is not None and cap == window:
            slot = cache.pos % cap
        else:
            slot = cache.pos
        k_new = jax.lax.dynamic_update_slice(
            cache.k, k, (0, 0, slot.astype(jnp.int32), 0)
        )
        v_new = jax.lax.dynamic_update_slice(
            cache.v, v, (0, 0, slot.astype(jnp.int32), 0)
        )
        new_cache = KVCache(k=k_new, v=v_new, pos=cache.pos + S)
        # mask: causal within the just-written block, plus only-written slots.
        # For the non-ring cache, slot index == absolute position; for the
        # ring buffer all resident entries are within the window (<= cap
        # past tokens), so "written" is the only constraint beyond causality
        # of the current block (whose slots are pos..pos+S-1 mod cap).
        kpos = jnp.arange(cap)[None, None, :]  # (1,1,cap) slot ids
        qabs = cache.pos + jnp.arange(S)[:, None]  # (S,1) absolute q positions
        if window is not None and cap == window:
            kslot_new = (cache.pos + jnp.arange(S)) % cap  # slots being written
            written = kpos < jnp.minimum(cache.pos + S, cap)
            # block-causality between the S new tokens themselves
            is_new = kpos == kslot_new[:, None]  # (S, cap)... align dims
            new_order = jnp.where(
                kpos[0] == kslot_new[:, None], jnp.arange(S)[:, None], -1
            )  # (S, cap): which new token wrote this slot (-1 = old)
            causal_new = (new_order <= jnp.arange(S)[:, None]) | (new_order < 0)
            valid = written[0] & causal_new
        else:
            valid = (kpos[0] <= qabs) & (kpos[0] < cache.pos + S)
        qf = (q.astype(jnp.float32)) * (cfg.head_dim ** -0.5)
        kf = k_new.astype(jnp.float32)
        vf = v_new.astype(jnp.float32)
        group = cfg.n_q_heads // cfg.n_kv_heads
        kf = jnp.repeat(kf, group, axis=1)
        vf = jnp.repeat(vf, group, axis=1)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
        logits = jnp.where(valid[None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf).astype(x.dtype)

    out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_q_heads * cfg.head_dim)
    return out @ params["wo"].astype(x.dtype), new_cache


def _split_s_decode(q, k_cache, v_cache, pos, mesh, ctx):
    """shard_map wrapper: cache seq-sharded over model; q replicated.

    Returns (B, Hq, D) attention output, replicated over the model axis.
    """
    from jax.sharding import PartitionSpec as P

    B = q.shape[0]
    cap = k_cache.shape[2]
    dspec = ctx.data_spec() if B % ctx.dp_total == 0 else None

    def body(q_loc, k_loc, v_loc, pos_loc):
        c_loc = k_loc.shape[2]
        tp_idx = jax.lax.axis_index(ctx.model_axis)
        slots = tp_idx * c_loc + jnp.arange(c_loc)  # global slot ids
        valid = slots[None, :] <= pos_loc  # causal: written slots only
        valid = jnp.broadcast_to(valid, (q_loc.shape[0], c_loc))
        o, m, l = partial_decode_attention(q_loc, k_loc, v_loc, valid)
        return combine_partial_attention(o, m, l, ctx.model_axis)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dspec, None, None, None),
            P(dspec, None, ctx.model_axis, None),
            P(dspec, None, ctx.model_axis, None),
            P(),
        ),
        out_specs=P(dspec, None, None),
    )(q, k_cache, v_cache, pos)


# ------------------------------------------------- split-S decode (serving) --


def partial_decode_attention(
    q: jnp.ndarray,  # (B, Hq, 1, D) — already scaled & roped
    k_shard: jnp.ndarray,  # (B, Hkv, C_shard, D) local cache slice
    v_shard: jnp.ndarray,
    valid: jnp.ndarray,  # (B, C_shard) bool
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """FlashDecoding-style partial attention over one cache shard.

    Returns (o_partial (B,Hq,D), m (B,Hq), l (B,Hq)) combinable across shards:
        o = Σ o_i·l_i·exp(m_i−m) / Σ l_i·exp(m_i−m),  m = max_i m_i
    Used inside shard_map with the cache sequence-sharded over the model axis;
    the combine is one psum per layer (DESIGN.md §5: bounds decode_32k memory).

    GQA is handled by *grouping q heads* (einsum free dim) instead of
    ``jnp.repeat`` on the cache — repeating materialised group× copies of the
    cache slice in f32 (8× HBM traffic, see EXPERIMENTS.md §Perf iteration 2);
    the cache is read once in its storage dtype with f32 accumulation.
    """
    B, Hq, _, D = q.shape
    Hkv = k_shard.shape[1]
    group = Hq // Hkv
    qg = q[:, :, 0, :].reshape(B, Hkv, group, D)
    logits = jnp.einsum(
        "bhgd,bhkd->bhgk", qg, k_shard,
        preferred_element_type=jnp.float32,
    )  # (B, Hkv, G, C)
    logits = jnp.where(valid[:, None, None], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)  # (B, Hkv, G)
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(valid[:, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum(
        "bhgk,bhkd->bhgd", p.astype(k_shard.dtype), v_shard,
        preferred_element_type=jnp.float32,
    )
    safe_m = jnp.where(jnp.isfinite(m), m, -1e30)
    return (
        o.reshape(B, Hq, D),
        safe_m.reshape(B, Hq),
        l.reshape(B, Hq),
    )


def combine_partial_attention(o, m, l, axis: str):
    """psum-combine of (o·scale, l·scale) with the global running max."""
    m_glob = jax.lax.pmax(m, axis)
    scale = jnp.exp(m - m_glob)
    o_sum = jax.lax.psum(o * scale[..., None], axis)
    l_sum = jax.lax.psum(l * scale, axis)
    return o_sum / jnp.maximum(l_sum, 1e-30)[..., None]
