"""Device-sharded partition execution: the ``data`` mesh axis made real.

The partial/combine decomposition in :mod:`repro.frame.blocking` is exactly a
map + all-reduce: partitions live on devices along the ``data`` mesh axis and
the combine lowers to collectives.  This module holds the device layer:

* :func:`data_mesh` — the process-wide 1-D ``data`` mesh (emulated multi-device
  CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, unchanged on
  a real TPU pod);
* :class:`ShardedPTable` — a PTable's numeric column blocks stacked into
  ``(Ppad, C, nb)`` device matrices with ``NamedSharding`` along ``data``
  (partition axis sharded, contiguous blocks of ``pl = Ppad/d`` partitions per
  device), cached on the (immutable) host table;
* sharded dispatches — describe/mean raws (merged on the host), groupby
  segment fold, value_counts psum, per-partition topk winners, and the
  partition-parallel join build/probe.  Each runs ONE shard_map over all
  partitions instead of P per-partition dispatches + a host merge loop.

Bit-for-bit contract: per-partition raws come from the *same* traced kernels
(:func:`repro.kernels.ops.stats_row_tiled` et al.) the host path dispatches,
at a shared row bucket whose extra all-masked tiles are exact no-ops — so the
numbers entering every combine are bit-identical.  The describe/mean merge
(means, Chan's m2 update) runs on the host over the sharded raws: a TPU
emulates f64, not to IEEE rounding, so an in-jit f64 merge differed from the
host merge in the last bits on the chip.  The groupby fold adds f32 partials
in f64 in global partition order and value_counts psums integer counts —
exact wherever the sums are exact in f64.
"""
from __future__ import annotations

import math
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels import ops
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

AXIS = "data"

# --------------------------------------------------------------------------- #
# mesh management                                                              #
# --------------------------------------------------------------------------- #

_MESH: Optional[Mesh] = None
_MESH_INERT = False
_MESH_LOCK = threading.Lock()


def data_mesh() -> Optional[Mesh]:
    """The process-wide 1-D ``data`` mesh over all local devices, or ``None``
    when sharded execution cannot run (single device, or a non-power-of-two
    device count — the balanced-tree combine needs pow-2 blocks).  A failure
    to enumerate devices or to build the mesh raises: it must not quietly
    turn the collective path into the host path."""
    global _MESH, _MESH_INERT
    if _MESH is not None:
        return _MESH
    if _MESH_INERT:
        return None
    with _MESH_LOCK:
        if _MESH is not None:
            return _MESH
        devs = jax.devices()
        d = len(devs)
        if d < 2 or (d & (d - 1)) != 0:
            _MESH_INERT = True
            return None
        _MESH = jax.make_mesh(
            (d,), (AXIS,), axis_types=(jax.sharding.AxisType.Auto,), devices=devs
        )
        return _MESH


def device_count() -> int:
    mesh = data_mesh()
    return int(mesh.devices.size) if mesh is not None else 1


# --------------------------------------------------------------------------- #
# mode + dispatch counters                                                     #
# --------------------------------------------------------------------------- #

_MODE = "auto"  # "auto" (planner decides) | "on" (force) | "off" (disable)


def set_mode(mode: str) -> None:
    global _MODE
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"sharded mode {mode!r} (want auto|on|off)")
    _MODE = mode


def mode() -> str:
    return _MODE


@contextmanager
def use_sharded(mode_: str):
    """Scoped sharded-dispatch mode (tests/benches force or disable)."""
    global _MODE
    prev = _MODE
    set_mode(mode_)
    try:
        yield
    finally:
        _MODE = prev


def sharded_available() -> bool:
    """True when sharded dispatch may run: a usable mesh and not forced off."""
    return _MODE != "off" and data_mesh() is not None


_COUNTS: Dict[str, int] = {}
_COUNTS_LOCK = threading.Lock()


def _count(op: str) -> None:
    with _COUNTS_LOCK:
        _COUNTS[op] = _COUNTS.get(op, 0) + 1


def dispatch_counts() -> Dict[str, int]:
    with _COUNTS_LOCK:
        return dict(_COUNTS)


def reset_dispatch_counts() -> None:
    with _COUNTS_LOCK:
        _COUNTS.clear()


# --------------------------------------------------------------------------- #
# sharded placement helpers                                                    #
# --------------------------------------------------------------------------- #


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def put_sharded(mesh: Mesh, x: np.ndarray) -> jnp.ndarray:
    """Place a host array on the mesh sharded along its leading axis."""
    return jax.device_put(x, NamedSharding(mesh, P(AXIS)))


def _padded_layout(nparts: int, mesh: Mesh) -> Tuple[int, int, int]:
    """(Ppad, pl, d): partitions padded to a pow-2 multiple of the device
    count, pl = Ppad // d contiguous partitions per device."""
    d = int(mesh.devices.size)
    ppad = _next_pow2(max(nparts, d))
    return ppad, ppad // d, d


def _common_bucket(nrows: Sequence[int]) -> int:
    """Shared row bucket for a stack of partitions: the largest partition's
    pad bucket, at least one kernel tile so fixed-_TILE scans divide it.
    Extra all-masked tiles are exact no-ops (see ops.masked_stats_batch)."""
    mx = max((int(n) for n in nrows), default=0)
    return max(ops.pad_len(mx), ops.TILE)


# --------------------------------------------------------------------------- #
# ShardedPTable — device-resident stats stack                                  #
# --------------------------------------------------------------------------- #


@dataclass
class ShardedPTable:
    """A PTable's numeric column blocks, device-resident and sharded along
    ``data``: ``xs``/``ms`` are ``(Ppad, C, nb)`` value/validity matrices with
    partition ``i`` of the host table at row ``i`` (rows ≥ nparts are all
    masked — exact-neutral padding)."""

    mesh: Mesh
    names: Tuple[str, ...]
    xs: jnp.ndarray  # (Ppad, C, nb) f32, sharded P("data")
    ms: jnp.ndarray  # (Ppad, C, nb) bool, sharded P("data")
    nparts: int
    ppad: int
    pl: int
    nb: int

    @classmethod
    def from_table(cls, table, names: Sequence[str]) -> Optional["ShardedPTable"]:
        """Build (or fetch the cached) sharded stats stack for ``table``.
        Returns ``None`` when no mesh is available or the table has no
        partitions/columns to stack.  Cached on the immutable table."""
        mesh = data_mesh()
        if mesh is None:
            return None
        key = tuple(names)
        cached = table.__dict__.get("_sharded_stats")
        if cached is not None and cached.names == key:
            return cached
        parts = table.partitions
        if not parts or not key:
            return None
        ppad, pl, d = _padded_layout(len(parts), mesh)
        nb = _common_bucket([p.nrows for p in parts])
        xs = np.zeros((ppad, len(key), nb), np.float32)
        ms = np.zeros((ppad, len(key), nb), bool)
        for i, part in enumerate(parts):
            n = part.nrows
            for c, name in enumerate(key):
                col = part.columns.get(name)
                if col is None or col.is_string:
                    return None
                xs[i, c, :n] = np.asarray(col.data, np.float32)
                ms[i, c, :n] = np.asarray(col.valid_mask())
        sh = cls(
            mesh=mesh, names=key,
            xs=put_sharded(mesh, xs), ms=put_sharded(mesh, ms),
            nparts=len(parts), ppad=ppad, pl=pl, nb=nb,
        )
        table.__dict__["_sharded_stats"] = sh
        return sh


# --------------------------------------------------------------------------- #
# sharded dispatches                                                           #
# --------------------------------------------------------------------------- #

# Every shard_map here runs with check_vma=False: scan carries start
# replicated and become device-varying, and replicated outputs come from
# all-gathered or psummed operands — neither is visible to the varying-axes
# checker.
_JITS: Dict[tuple, object] = {}


def _jit_for(key: tuple, builder):
    fn = _JITS.get(key)
    if fn is None:
        fn = builder()
        _JITS[key] = fn
    return fn


def _make_stats_raws(mesh: Mesh, pl: int, C: int, nb: int):
    def shard_fn(xs, ms):
        rows = [
            ops.stats_row_tiled(xs[p, c], ms[p, c], ops.TILE)
            for p in range(pl)
            for c in range(C)
        ]
        return jnp.stack(rows).reshape(pl, C, 5)

    return jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS), check_vma=False,
        )
    )


def stats_raws(st: ShardedPTable) -> np.ndarray:
    """One dispatch covering every partition: per-partition (count, sum, m2,
    min, max) f32 raws, (Ppad, C, 5) — the sharded flavor of the executor's
    UnitBatch (k partitions × d devices in one call).  Rows are bit-identical
    to the host per-partition kernel, so slicing row i and feeding it through
    backend._stats_from_raw reproduces the host partial exactly."""
    fn = _jit_for(
        ("stats_raws", st.pl, len(st.names), st.nb),
        lambda: _make_stats_raws(st.mesh, st.pl, len(st.names), st.nb),
    )
    out = np.asarray(fn(st.xs, st.ms))
    _count("stats")
    return out


def _make_segment_fold(
    mesh: Mesh, pl: int, d: int, nb: int, nbuckets: int,
    S: int, V: int, modes: Tuple[str, ...], valid_idx: Tuple[int, ...],
):
    def shard_fn(keys, values, valids):
        # keys (pl, nb) i32; values (pl, S, nb) f32; valids (pl, V, nb) bool
        reds_l, cnts_l = [], []
        for p in range(pl):
            r, c = ops.segment_batch_body(
                keys[p],
                tuple(values[p, s] for s in range(S)),
                tuple(valids[p, v] for v in range(V)),
                nbuckets, modes, valid_idx, ops.TILE,
            )
            reds_l.append(r)
            cnts_l.append(c)
        reds = jnp.stack(reds_l).astype(jnp.float64)  # (pl, S, B)
        cnts = jnp.stack(cnts_l).astype(jnp.float64)  # (pl, V, B)
        if S == 0:
            # value_counts: integer counts are order-independent in f64 —
            # local sequential fold then one psum, both exact.
            local = cnts.sum(axis=0)
            return reds[0:0].reshape(0, nbuckets), jax.lax.psum(local, AXIS)
        # groupby: the host combine is a flat left fold (np.add.at over
        # concatenated payloads in partition order) — replay it exactly:
        # all-gather the per-partition contributions and fold sequentially
        # in global partition order inside the jit.
        g_r = jax.lax.all_gather(reds, AXIS).reshape(d * pl, S, nbuckets)
        g_c = jax.lax.all_gather(cnts, AXIS).reshape(d * pl, V, nbuckets)

        def body(p, acc):
            racc, cacc = acc
            r = g_r[p]
            rows = []
            for s in range(S):
                if modes[s] == "sum":
                    rows.append(racc[s] + r[s])
                elif modes[s] == "min":
                    rows.append(jnp.minimum(racc[s], r[s]))
                else:
                    rows.append(jnp.maximum(racc[s], r[s]))
            return (jnp.stack(rows), cacc + g_c[p])

        init_rows = [
            jnp.full(
                nbuckets,
                jnp.inf if modes[s] == "min"
                else (-jnp.inf if modes[s] == "max" else 0.0),
                jnp.float64,
            )
            for s in range(S)
        ]
        racc, cacc = jax.lax.fori_loop(
            0, d * pl, body,
            (jnp.stack(init_rows), jnp.zeros((V, nbuckets), jnp.float64)),
        )
        return racc, cacc

    return jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P(AXIS)), out_specs=(P(), P()),
            check_vma=False,
        )
    )


def segment_fold(
    mesh: Mesh,
    keys: jnp.ndarray,    # (Ppad, nb) i32 sharded
    values: jnp.ndarray,  # (Ppad, S, nb) f32 sharded
    valids: jnp.ndarray,  # (Ppad, V, nb) bool sharded
    nbuckets: int,
    modes: Tuple[str, ...],
    valid_idx: Tuple[int, ...],
    pl: int,
    d: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """One dispatch: per-partition segment reductions + exact f64 fold in
    global partition order.  Returns (reds (S, B), cnts (V, B)) f64 — feed
    through backend._groupby_from_raw / _vc_from_raw as ONE synthetic partial."""
    nb = int(keys.shape[-1])
    S = int(values.shape[1])
    V = int(valids.shape[1])
    with jax.enable_x64(True):
        fn = _jit_for(
            ("segment_fold", pl, d, nb, nbuckets, S, V, modes, valid_idx),
            lambda: _make_segment_fold(
                mesh, pl, d, nb, nbuckets, S, V, modes, valid_idx
            ),
        )
        reds, cnts = fn(keys, values, valids)
        out = (np.asarray(reds), np.asarray(cnts))
    _count("value_counts" if S == 0 else "groupby")
    return out


def _make_topk_winners(mesh: Mesh, pl: int, nb: int, k: int, largest: bool):
    def shard_fn(kf):  # (pl, nb) f32
        return jnp.stack([ops.topk_body(kf[p], k, largest) for p in range(pl)])

    return jax.jit(
        jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(AXIS),), out_specs=P(AXIS))
    )


def topk_winners(
    mesh: Mesh, kf32: jnp.ndarray, k: int, largest: bool, pl: int
) -> np.ndarray:
    """One dispatch: per-partition top-k winner values for every partition,
    (Ppad, k) f32.  Only winners[-1] (the per-partition k-th value) is
    consumed — backend._limit_select does the host-side candidate pick, so
    results stay bit-identical to the per-partition topk path."""
    nb = int(kf32.shape[-1])
    fn = _jit_for(
        ("topk_winners", pl, nb, k, largest),
        lambda: _make_topk_winners(mesh, pl, nb, k, largest),
    )
    out = np.asarray(fn(kf32))
    _count("topk")
    return out


# --------------------------------------------------------------------------- #
# partition-parallel join: sharded sorted build + local probe + psum combine   #
# --------------------------------------------------------------------------- #


@dataclass
class ShardedJoinBuild:
    """The right side's (key, row-id) pairs, range-free: padded to d equal
    shards, each shard locally sorted on device.  Invalid/padding rows carry
    (+inf, −1).  Intra-shard duplicate keys are rejected at build; duplicates
    straddling shards surface at probe time via the psum'd hit count."""

    mesh: Mesh
    keys_sorted: jnp.ndarray  # (d*ml,) f32 sharded, each shard ascending
    ids_sorted: jnp.ndarray   # (d*ml,) i32 sharded
    ml: int
    d: int
    nbytes: int


def _make_join_build(mesh: Mesh, ml: int):
    def shard_fn(keys, ids):  # (ml,) f32 / (ml,) i32
        ks, ids_s = jax.lax.sort((keys, ids), num_keys=1)
        valid = ids_s >= 0
        dup = (ks[1:] == ks[:-1]) & valid[1:] & valid[:-1]
        dups = jax.lax.psum(dup.sum().astype(jnp.int32), AXIS)
        return ks, ids_s, dups

    return jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS)), out_specs=(P(AXIS), P(AXIS), P()),
            check_vma=False,
        )
    )


def join_build(keys_f32: np.ndarray, ids_i32: np.ndarray) -> ShardedJoinBuild:
    """Shard the right side's keys across ``data`` and sort each shard on its
    own device — the build never materialises a single sorted array on one
    host.  Raises on intra-shard duplicate valid keys (dim-table contract)."""
    mesh = data_mesh()
    if mesh is None:
        raise RuntimeError("join_build: no data mesh")
    d = int(mesh.devices.size)
    m = int(keys_f32.shape[0])
    ml = ops.pad_len(-(-max(m, 1) // d))
    total = d * ml
    kp = np.full(total, np.inf, np.float32)
    ip = np.full(total, -1, np.int32)
    kp[:m] = keys_f32
    ip[:m] = ids_i32
    fn = _jit_for(("join_build", d, ml), lambda: _make_join_build(mesh, ml))
    ks, ids_s, dups = fn(put_sharded(mesh, kp), put_sharded(mesh, ip))
    _count("join_build")
    if int(dups) > 0:
        raise ValueError("join: right-side keys must be unique (dim-table join)")
    return ShardedJoinBuild(
        mesh=mesh, keys_sorted=ks, ids_sorted=ids_s, ml=ml, d=d,
        nbytes=int(keys_f32.nbytes),
    )


def _make_join_probe(mesh: Mesh, ml: int, nb: int):
    def shard_fn(ks, ids, lk):  # (ml,) / (ml,) / (nb,) replicated
        pos = jnp.searchsorted(ks, lk, side="left")
        posc = jnp.clip(pos, 0, ml - 1)
        hit = (ks[posc] == lk) & (ids[posc] >= 0)
        hitc = jax.lax.psum(hit.astype(jnp.int32), AXIS)
        gid = jax.lax.psum(jnp.where(hit, ids[posc], 0), AXIS)
        return hitc, gid

    return jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P()), out_specs=(P(), P()),
            check_vma=False,
        )
    )


def join_probe(
    build: ShardedJoinBuild, l_keys_f32: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Probe left keys against every shard locally; combine with two psums
    (hit count + hit row-id — only the owning shard contributes).  Returns
    (gather row-ids, hit) for the left partition.  A psum'd hit count > 1
    means duplicate right keys straddled shards: same ValueError the host
    build raises, just detected at first probe."""
    n = int(l_keys_f32.shape[0])
    nb = ops.pad_len(n)
    lp = np.full(nb, np.nan, np.float32)
    lp[:n] = l_keys_f32
    fn = _jit_for(
        ("join_probe", build.d, build.ml, nb),
        lambda: _make_join_probe(build.mesh, build.ml, nb),
    )
    hitc, gid = fn(build.keys_sorted, build.ids_sorted, jnp.asarray(lp))
    _count("join_probe")
    hitc = np.asarray(hitc)[:n]
    gid = np.asarray(gid)[:n]
    if (hitc > 1).any():
        raise ValueError("join: right-side keys must be unique (dim-table join)")
    return np.maximum(gid, 0).astype(np.intp), hitc == 1


# --------------------------------------------------------------------------- #
# seed API (kept): the original dry-run formulations                           #
# --------------------------------------------------------------------------- #


def masked_stats_local(x: jnp.ndarray, mask: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """Single-pass fused stats over a masked column (the `masked_stats`
    kernel's contract): (count, sum, m2, min, max), where m2 is the centered
    second moment Σ m·(x − local mean)² — a raw sum of squares cancels
    catastrophically when |mean| ≫ std."""
    m = mask.astype(x.dtype)
    n = jnp.sum(m)
    s = jnp.sum(x * m)
    mean = s / jnp.maximum(n, 1)
    d = (x - mean) * m
    m2 = jnp.sum(d * d)
    big = jnp.asarray(jnp.inf, x.dtype)
    mn = jnp.min(jnp.where(mask, x, big))
    mx = jnp.max(jnp.where(mask, x, -big))
    return n, s, m2, mn, mx


def make_distributed_describe(mesh: Mesh, axis: str = "data"):
    """describe over a column sharded along ``axis``: local fused pass + psum.

    Per-shard moments about the local mean are combined with the parallel
    (Chan-style) variance formula: total m2 = Σ_i (m2_i + n_i·(mean_i −
    mean)²), realised as a second psum once the global mean is known.

    Returns a jit-compiled fn (x, mask) -> (count, mean, std, min, max).
    """

    def _local(x, mask):
        n_l, s_l, m2_l, mn, mx = masked_stats_local(x, mask)
        n = jax.lax.psum(n_l, axis)
        s = jax.lax.psum(s_l, axis)
        mn = jax.lax.pmin(mn, axis)
        mx = jax.lax.pmax(mx, axis)
        mean = s / jnp.maximum(n, 1)
        lmean = s_l / jnp.maximum(n_l, 1)
        delta = lmean - mean
        m2 = jax.lax.psum(m2_l + delta * delta * n_l, axis)
        var = jnp.maximum(m2, 0.0) / jnp.maximum(n, 1)
        denom = jnp.maximum(n - 1, 1)
        std = jnp.sqrt(var * n / denom)
        return jnp.stack([n, mean, std, mn, mx])

    sharded = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(),
    )
    return jax.jit(sharded)


def make_distributed_groupby_sum(mesh: Mesh, n_buckets: int, axis: str = "data"):
    """groupby-sum with integer keys in [0, n_buckets): local segment_sum into
    a dense bucket vector (the `segment_reduce` kernel's contract) + psum.

    Returns jit fn (keys:int32[n], values:f32[n], valid:bool[n])
    -> (sums[f32,B], counts[f32,B]).
    """

    def _local(keys, values, valid):
        v = jnp.where(valid, values, 0.0)
        c = valid.astype(values.dtype)
        sums = jax.ops.segment_sum(v, keys, num_segments=n_buckets)
        counts = jax.ops.segment_sum(c, keys, num_segments=n_buckets)
        return jax.lax.psum(sums, axis), jax.lax.psum(counts, axis)

    sharded = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(), P()),
    )
    return jax.jit(sharded)


def shard_column(
    mesh: Mesh, x: jnp.ndarray, axis: str = "data"
) -> jnp.ndarray:
    """Place a host column onto the mesh sharded along ``axis``."""
    return jax.device_put(x, NamedSharding(mesh, P(axis)))
