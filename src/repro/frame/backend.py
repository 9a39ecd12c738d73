"""Pluggable columnar kernel backend for the frame layer.

The blocking operators in :mod:`repro.frame.blocking` are written as scalar
numpy partial/combine pairs — correct, but simulation-grade.  This module is
the dispatch seam that routes the same partial computations to the jit'd
kernel dispatchers in :mod:`repro.kernels.ops`:

========================  =============================================
frame partial             kernel
========================  =============================================
``partial_stats``         ``masked_stats`` (batched over columns)
``partial_groupby``       ``segment_reduce`` (dictionary-coded keys)
``partial_value_counts``  ``segment_reduce`` (counts only)
``partial_sort(limit=k)`` ``topk`` (threshold + small residual argsort)
``partial_sort`` (full)   ``argsort_f64`` (exact 3×f32 split + ``lax.sort``)
``merge_sort`` (full)     sample-sort range split + ``argsort_f64``
``join_partition``        ``join_probe`` (sorted right side, counting probe)
``select_rows``           ``filter_compact`` (per-column compaction)
========================  =============================================

Backend selection is per-call via a policy chain, strongest first:

1. explicit ``backend=`` argument,
2. a process-global override (``set_frame_backend`` / ``use_backend``),
3. the ``REPRO_FRAME_BACKEND`` environment variable,
4. the engine's configured default (``Engine(kernel_backend=...)``),
5. ``"numpy"``.

``"numpy"`` is the scalar host path; ``"xla"``/``"interpret"``/``"pallas"``
map onto the kernel dispatchers' backends.  Every accelerated function falls
back to the numpy implementation for shapes it cannot handle (string columns,
callable aggs, empty partitions, non-dictionary group keys), so the frame
layer can call these unconditionally.

Note on precision: the accelerated backends accumulate in float32 (the TPU
kernels' native dtype); the numpy path uses float64.  Parity is to ~1e-4
relative, which the backend-parity tests pin down.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import faults as _faults
from ..kernels import ops
from . import blocking as B
from .blocking import BUILTIN_AGGS, ColStats
from .table import Column, Partition, PTable

logger = logging.getLogger("repro.frame.backend")

BACKENDS = ("numpy", "xla", "interpret", "pallas")
ENV_VAR = "REPRO_FRAME_BACKEND"

_GLOBAL: Optional[str] = None


def _check(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(f"unknown frame backend {name!r}; expected one of {BACKENDS}")
    return name


def set_frame_backend(name: Optional[str]) -> None:
    """Process-global backend override (None = clear)."""
    global _GLOBAL
    _GLOBAL = _check(name) if name is not None else None


@contextmanager
def use_backend(name: Optional[str]):
    """Scoped backend override (tests / benchmarks)."""
    global _GLOBAL
    prev = _GLOBAL
    _GLOBAL = _check(name) if name is not None else None
    try:
        yield
    finally:
        _GLOBAL = prev


@dataclass
class BackendPolicy:
    """Per-engine backend resolution (engine config is the weakest override)."""

    engine_default: Optional[str] = None

    def resolve(self, override: Optional[str] = None) -> str:
        for cand in (override, _GLOBAL, os.environ.get(ENV_VAR), self.engine_default):
            if cand:
                return _check(cand)
        return "numpy"

    def resolve_tier(self, override: Optional[str] = None) -> Tuple[str, str]:
        """``resolve()`` plus WHICH precedence tier answered.

        The cost-based planner (``frame/planner.py``) only governs the two
        weakest tiers — ``"engine"`` (the engine's configured default) and
        ``"default"`` (nothing configured) — so an explicit per-call /
        ``use_backend`` / env override stays an absolute instruction and
        bypasses planning entirely."""
        for cand, tier in (
            (override, "call"),
            (_GLOBAL, "global"),
            (os.environ.get(ENV_VAR), "env"),
            (self.engine_default, "engine"),
        ):
            if cand:
                return _check(cand), tier
        return "numpy", "default"


_DEFAULT_POLICY = BackendPolicy()


def active_backend(override: Optional[str] = None) -> str:
    return _DEFAULT_POLICY.resolve(override)


def _kernel(backend: str):
    """Route repro.kernels.ops dispatch to the requested kernel backend.

    Thread-local: the real-mode background worker executes units concurrently
    with foreground interactions, so a process-global save/restore would race
    (and could strand the global override in the wrong state)."""
    return ops.local_backend(backend)


def _call(family: str, bk: str, parts: Sequence[Partition]) -> obs.span:
    """The enqueue of one kernel call over ``parts`` (host work until the
    device has it), as a ``dispatch.call`` span."""
    return obs.span("dispatch.call", family=family, backend=bk,
                    rows=sum(p.nrows for p in parts),
                    bucket=ops.pad_len(parts[0].nrows))


def _readback(dev, dtype=None):
    """Device result (an array or a tuple of arrays) → host numpy, as a
    ``dispatch.readback`` span.  Where nothing waited for the device first,
    the copy waits for it too."""
    arrays = dev if isinstance(dev, tuple) else (dev,)
    with obs.span("dispatch.readback", bytes=sum(a.nbytes for a in arrays)):
        out = tuple(np.asarray(a, dtype) for a in arrays)
    return out if isinstance(dev, tuple) else out[0]


def _fetch(dev, dtype=None):
    """A synchronous kernel result on the host: a ``dispatch.wait`` span for
    the device to finish, then the ``dispatch.readback`` copy."""
    with obs.span("dispatch.wait"):
        jax.block_until_ready(dev)
    return _readback(dev, dtype)


# --------------------------------------------------------------------------- #
# runtime fault tolerance: per-(op, backend) circuit breakers                  #
#                                                                              #
# The eligibility gates above/below this module are *ahead-of-time* — they     #
# route shapes a kernel cannot handle.  Kernels can also fail at RUN time      #
# (XLA RESOURCE_EXHAUSTED, a lowering bug on a new shape, injected chaos       #
# faults).  Every kernel call therefore goes through _guarded(): a runtime     #
# exception falls back to the numpy reference for THAT dispatch, and repeated  #
# failures trip a circuit breaker so subsequent dispatches skip the broken     #
# kernel entirely until a half-open probe proves it healthy again.            #
#                                                                              #
#   closed ──(threshold consecutive failures)──▶ open                          #
#   open ──(backoff elapsed; next dispatch is the probe)──▶ half-open          #
#   half-open ──(probe succeeds)──▶ closed    ──(probe fails)──▶ open          #
#                                                                              #
# Breaker state is keyed (op-family, backend) and process-global — kernel      #
# health is a property of the process (compiled executables, device state),    #
# not of any one engine.                                                       #
# --------------------------------------------------------------------------- #


@dataclass
class _BreakerState:
    state: str = "closed"  # "closed" | "open" | "half_open"
    consecutive_failures: int = 0
    opened_at: float = 0.0
    open_count: int = 0  # times tripped (drives the exponential backoff)
    failures: int = 0
    successes: int = 0
    fallbacks: int = 0  # dispatches served by numpy while not closed
    last_error: str = ""


class BreakerBoard:
    """Thread-safe registry of per-(op, backend) circuit breakers."""

    def __init__(
        self,
        failure_threshold: int = 3,
        backoff_s: float = 5.0,
        backoff_max_s: float = 300.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.failure_threshold = failure_threshold
        self.backoff_s = backoff_s
        self.backoff_max_s = backoff_max_s
        self.clock = clock
        self._lock = threading.Lock()
        self._states: Dict[Tuple[str, str], _BreakerState] = {}

    def _state(self, op: str, bk: str) -> _BreakerState:
        st = self._states.get((op, bk))
        if st is None:
            st = self._states[(op, bk)] = _BreakerState()
        return st

    def _backoff(self, st: _BreakerState) -> float:
        return min(self.backoff_s * (2 ** max(st.open_count - 1, 0)), self.backoff_max_s)

    def allow(self, op: str, bk: str) -> bool:
        """May this dispatch try the kernel?  An open breaker whose backoff
        has elapsed transitions to half-open and admits exactly this call as
        the recovery probe; further calls are refused until the probe's
        verdict arrives."""
        with self._lock:
            st = self._state(op, bk)
            if st.state == "closed":
                return True
            if st.state == "open" and (
                self.clock() - st.opened_at >= self._backoff(st)
            ):
                st.state = "half_open"
                return True  # this dispatch is the probe
            st.fallbacks += 1
            return False

    def record_success(self, op: str, bk: str) -> None:
        with self._lock:
            st = self._state(op, bk)
            if st.state == "half_open":
                logger.info("breaker (%s, %s) closed: probe succeeded", op, bk)
            st.state = "closed"
            st.consecutive_failures = 0
            st.successes += 1

    def record_failure(self, op: str, bk: str, error: str = "") -> None:
        with self._lock:
            st = self._state(op, bk)
            st.failures += 1
            st.consecutive_failures += 1
            st.last_error = error[:200]
            if st.state == "half_open" or (
                st.state == "closed"
                and st.consecutive_failures >= self.failure_threshold
            ):
                st.state = "open"
                st.opened_at = self.clock()
                st.open_count += 1
                logger.warning(
                    "breaker (%s, %s) OPEN after %d consecutive failure(s); "
                    "numpy fallback for %.1fs (%s)",
                    op, bk, st.consecutive_failures, self._backoff(st), error,
                )

    def is_closed(self, op: str, bk: str) -> bool:
        """Read-only planning gate (no probe grant, no fallback counting):
        batch planners decline fusion while a breaker is not closed, pushing
        units through the per-partition paths where _guarded handles the
        fallback — and the half-open recovery probe — one dispatch at a time."""
        with self._lock:
            return self._state(op, bk).state == "closed"

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            return {
                f"{op}|{bk}": {
                    "state": st.state,
                    "failures": st.failures,
                    "successes": st.successes,
                    "fallbacks": st.fallbacks,
                    "open_count": st.open_count,
                    "last_error": st.last_error,
                }
                for (op, bk), st in sorted(self._states.items())
            }

    def reset(self) -> None:
        with self._lock:
            self._states.clear()


_BOARD = BreakerBoard()


def breaker_board() -> BreakerBoard:
    return _BOARD


def reset_breakers() -> None:
    """Clear all breaker state (tests / between benchmark phases)."""
    _BOARD.reset()


# the backend that actually served the current unit's dispatch — consumed by
# the frame runtime so calibration samples (and the bench JSON built from
# them) attribute time to the path that really ran, not the one requested
_SERVED = threading.local()
# process-wide tally of guarded dispatches by (op, serving backend): the
# chip smoke reads it to prove no kernel dispatch was served by numpy
_SERVED_COUNTS: Dict[Tuple[str, str], int] = {}
_SERVED_LOCK = threading.Lock()


def note_reset() -> None:
    _SERVED.backend = None
    _SERVED.reason = None


def served_backend(default: str) -> Tuple[str, Optional[str]]:
    """(backend that served the last guarded dispatch, fallback reason)."""
    return (
        getattr(_SERVED, "backend", None) or default,
        getattr(_SERVED, "reason", None),
    )


def served_counts() -> Dict[Tuple[str, str], int]:
    """Guarded dispatches so far, keyed (op family, backend that served)."""
    with _SERVED_LOCK:
        return dict(_SERVED_COUNTS)


def reset_served_counts() -> None:
    with _SERVED_LOCK:
        _SERVED_COUNTS.clear()


def _tally(op: str, bk: str) -> None:
    with _SERVED_LOCK:
        _SERVED_COUNTS[(op, bk)] = _SERVED_COUNTS.get((op, bk), 0) + 1


def _note(op: str, bk: str, reason: Optional[str]) -> None:
    _SERVED.backend = bk
    _SERVED.reason = reason
    _tally(op, bk)


# A kernel the platform refuses to lower or compile fails on every call: a
# numpy fallback would only hide that the device never ran.  Such refusals
# propagate; run-time failures (resource exhaustion, injected faults) stay
# absorbed and scored against the breaker.
_REFUSAL_TYPES = ("LoweringException", "NotImplementedError")
_REFUSAL_MARKERS = (
    "pallas tpu lowering",
    "only interpret mode is supported",
    "mosaic",
    "failed to compile",
)


def _is_refusal(exc: BaseException) -> bool:
    if isinstance(exc, _faults.InjectedFault):
        return False
    if type(exc).__name__ in _REFUSAL_TYPES:
        return True
    msg = str(exc).lower()
    return any(m in msg for m in _REFUSAL_MARKERS)


def _guarded(op: str, bk: str, kernel_fn: Callable[[], Any],
             fallback_fn: Callable[[], Any]) -> Any:
    """Runtime dispatch guard: breaker gate → fault injection → kernel call;
    a run-time exception is absorbed into a numpy fallback for this dispatch
    and scored against the (op, backend) breaker.  The foreground interactive
    path rides the same guard, which is what makes user-visible results
    immune to kernel runtime failures.  A lowering or compile refusal
    (``_is_refusal``) raises instead."""
    if not _BOARD.allow(op, bk):
        _note(op, "numpy", "breaker_open")
        return fallback_fn()
    try:
        mode = _faults.fire("kernel", op=op)  # chaos: may raise / sleep
        if mode == "corrupt":
            # model: the kernel returned garbage and validation caught it
            raise _faults.InjectedFault(f"corrupted kernel output at {op}")
        out = kernel_fn()
    except Exception as exc:
        if _is_refusal(exc):
            raise
        _BOARD.record_failure(op, bk, error=f"{type(exc).__name__}: {exc}")
        _note(op, "numpy", "runtime_error")
        logger.warning(
            "kernel dispatch (%s, %s) failed at run time (%s: %s); "
            "numpy fallback for this dispatch",
            op, bk, type(exc).__name__, exc,
        )
        return fallback_fn()
    _BOARD.record_success(op, bk)
    _note(op, bk, None)
    return out


@contextmanager
def _breaker_watch(op: str, bk: str):
    """Batched dispatches don't fall back per-call (the whole batch raises to
    the executor, whose fault boundary quarantines the node) — but their
    run-time failures must still score the breaker so subsequent planning
    declines the broken kernel.  A lowering or compile refusal raises without
    scoring it.  Fires the kernel chaos site on entry, like _guarded."""
    mode = _faults.fire("kernel", op=op)  # may raise — counted below
    try:
        if mode == "corrupt":
            raise _faults.InjectedFault(f"corrupted kernel output at {op}")
        yield
    except Exception as exc:
        if not _is_refusal(exc):
            _BOARD.record_failure(op, bk, error=f"{type(exc).__name__}: {exc}")
        raise
    else:
        _BOARD.record_success(op, bk)
        _tally(op, bk)


# --------------------------------------------------------------------------- #
# device-resident column cache                                                 #
#                                                                              #
# Columns are immutable by construction (every frame op builds new Columns),   #
# so the f32/int32 device representation each kernel consumes is converted     #
# once and stashed on the Column instance.  This is the accelerated engine's   #
# data model — columns live device-resident between think-time quanta — and    #
# it is what makes repeated partials cheap: steady-state calls skip the        #
# host-side dtype conversion and transfer entirely.  Cost: one extra f32 copy  #
# per numeric column touched by a kernel backend.                              #
# --------------------------------------------------------------------------- #


def _dev_f32(col: Column):
    dev = col.__dict__.get("_dev_f32")
    if dev is None:
        dev = ops.upload(col.data, np.float32)
        col.__dict__["_dev_f32"] = dev
    return dev


def _dev_i32(col: Column):
    dev = col.__dict__.get("_dev_i32")
    if dev is None:
        dev = ops.upload(col.data, np.int32)
        col.__dict__["_dev_i32"] = dev
    return dev


def _dev_valid(col: Column):
    dev = col.__dict__.get("_dev_valid")
    if dev is None:
        with obs.span("dispatch.prep"):
            valid = col.valid_mask()
        dev = ops.upload(valid)
        col.__dict__["_dev_valid"] = dev
    return dev


def warm_device_cache(table) -> None:
    """Upload every partition's columns into the device-resident cache
    (production preloading: subsequent think-time partials skip all
    host→device transfers and are purely dispatch/compute bound).  Also
    pre-builds the stacked describe matrices (`_dev_stats_stack`), the other
    per-partition device artefact the steady state relies on."""
    for part in table.partitions:
        for name in part.order:
            c = part.columns[name]
            if c.is_string or c.data.dtype.kind in "iu":
                _dev_i32(c)
            if not c.is_string:
                _dev_f32(c)
            _dev_valid(c)
        numeric = B.numeric_columns(part)
        if numeric and part.nrows:
            _dev_stats_stack(part, numeric)


# --------------------------------------------------------------------------- #
# describe / mean — masked_stats                                               #
# --------------------------------------------------------------------------- #


def _dev_stats_stack(part: Partition, names: Sequence[str]):
    """The stacked + shape-bucketed (C, nb) value/validity matrices, cached
    per partition so steady-state describe partials skip all host work."""
    key = tuple(names)
    cached = part.__dict__.get("_dev_stats")
    if cached is None or cached[0] != key:
        nb = ops.pad_len(part.nrows)
        pad = nb - part.nrows
        xs = jnp.stack([_dev_f32(part.columns[n]) for n in names])
        ms = jnp.stack([_dev_valid(part.columns[n]) for n in names])
        if pad:
            xs = jnp.pad(xs, ((0, 0), (0, pad)))
            ms = jnp.pad(ms, ((0, 0), (0, pad)), constant_values=False)
        cached = (key, xs, ms)
        part.__dict__["_dev_stats"] = cached
    return cached[1], cached[2]


def _stats_from_raw(names: Sequence[str], raw: np.ndarray) -> Dict[str, ColStats]:
    """(C, 5) kernel rows of (count, sum, m2, min, max) → per-column
    ColStats — the shared host postprocessing of the batched and unbatched
    paths (bit-for-bit by construction).  The kernels carry the centered
    second moment directly (Chan's pairwise update), so no ss − s²/n
    conversion happens here — that difference cancels catastrophically in
    f32 once |mean| ≫ std."""
    out: Dict[str, ColStats] = {}
    for i, name in enumerate(names):
        count, s, m2, mn, mx = raw[i]
        if count == 0:
            out[name] = ColStats(0.0, 0.0, 0.0, np.inf, -np.inf)
        else:
            mean = s / count
            out[name] = ColStats(
                float(count), float(mean), float(max(m2, 0.0)), float(mn), float(mx)
            )
    return out


def partial_stats(
    part: Partition,
    cols: Optional[Sequence[str]] = None,
    backend: Optional[str] = None,
) -> Dict[str, ColStats]:
    bk = active_backend(backend)
    names = list(cols) if cols is not None else B.numeric_columns(part)
    if bk == "numpy" or not names or part.nrows == 0:
        return B.partial_stats(part, cols)

    def _run():
        with obs.span("dispatch.prep"):
            xs, ms = _dev_stats_stack(part, names)
        with _call("stats", bk, [part]), _kernel(bk):
            raw = ops.masked_stats_batch(xs, ms)
        return _stats_from_raw(names, _fetch(raw, np.float64))

    return _guarded("stats", bk, _run, lambda: B.partial_stats(part, cols))


# --------------------------------------------------------------------------- #
# groupby / value_counts — segment_reduce on dictionary codes                  #
# --------------------------------------------------------------------------- #

_SEG_MODE = {"sum": "sum", "count": "sum", "mean": "sum", "min": "min", "max": "max"}


def _groupby_supported(part: Partition, by: str, aggs, topk_keys) -> bool:
    key_col = part.columns.get(by)
    if key_col is None or key_col.dictionary is None:
        return False  # segment_reduce needs dense [0, nb) codes
    if topk_keys is not None or part.nrows == 0:
        return False
    for _, col, fn in aggs:
        if callable(fn) or fn not in BUILTIN_AGGS:
            return False
        if part.columns[col].is_string:
            return False
    return True


def _groupby_plan(part: Partition, by: str, aggs) -> tuple:
    """Assemble ONE batched kernel call for the whole agg set.  Validity rows
    are deduplicated by the agg column's mask identity — unmasked columns
    (and key presence) share a single count row instead of paying per-agg
    count passes.  Returns (keys, values, valids, modes, valid_idx, agg_plan);
    the plan *structure* (modes, valid_idx, per-agg rows) depends only on
    which agg columns carry masks, so same-layout partitions can share one
    fused multi-partition dispatch."""
    key_col = part.columns[by]
    kvalid = _dev_valid(key_col)
    values: list = []
    modes: list = []
    valid_idx: list = []
    valids: list = [kvalid]  # row 0: key presence
    valid_row_of: Dict[int, int] = {}
    agg_plan: list = []  # (out_name, fn, value_row | None, valid_row)
    for out_name, col, fn in aggs:
        vcol = part.columns[col]
        if vcol.mask is None:
            vrow = 0
        else:
            key = id(vcol.mask)
            vrow = valid_row_of.get(key)
            if vrow is None:
                vrow = len(valids)
                valids.append(kvalid & _dev_valid(vcol))
                valid_row_of[key] = vrow
        if fn == "count":
            agg_plan.append((out_name, fn, None, vrow))
            continue
        values.append(_dev_f32(vcol))
        modes.append(_SEG_MODE[fn])
        valid_idx.append(vrow)
        agg_plan.append((out_name, fn, len(values) - 1, vrow))
    return _dev_i32(key_col), values, valids, modes, valid_idx, agg_plan


def _groupby_from_raw(
    key_dtype, agg_plan, reds: np.ndarray, cnts: np.ndarray
) -> dict:
    """Kernel rows → the dense partial-groupby dict (shared by the batched and
    unbatched paths — bit-for-bit by construction)."""
    reds = np.asarray(reds, np.float64)
    cnts = np.asarray(cnts, np.float64)
    present = cnts[0] > 0
    dense: Dict[str, Tuple[str, Any]] = {}
    for out_name, fn, srow, vrow in agg_plan:
        if fn == "sum":
            dense[out_name] = ("sum", reds[srow][present])
        elif fn == "count":
            dense[out_name] = ("sum", cnts[vrow][present])
        elif fn == "mean":
            dense[out_name] = ("sum_count", (reds[srow][present], cnts[vrow][present]))
        else:  # min / max: empty (all-null) groups keep the ±inf neutral
            dense[out_name] = (fn, reds[srow][present])
    uniq = np.nonzero(present)[0].astype(key_dtype)
    return {"keys": uniq, "aggs": dense}


def partial_groupby(
    part: Partition,
    by: str,
    aggs: Sequence[Tuple[str, str, Any]],
    topk_keys: Optional[int] = None,
    backend: Optional[str] = None,
) -> dict:
    bk = active_backend(backend)
    if bk == "numpy" or not _groupby_supported(part, by, aggs, topk_keys):
        return B.partial_groupby(part, by, aggs, topk_keys)
    key_col = part.columns[by]
    nb = len(key_col.dictionary)

    def _run():
        with obs.span("dispatch.prep"):
            keys, values, valids, modes, valid_idx, agg_plan = _groupby_plan(
                part, by, aggs
            )
        with _call("groupby", bk, [part]), _kernel(bk):
            reds, cnts = ops.segment_reduce_batch(
                keys, values, valids, nb, modes, valid_idx
            )
        reds, cnts = _fetch((reds, cnts))
        return _groupby_from_raw(key_col.data.dtype, agg_plan, reds, cnts)

    return _guarded(
        "groupby", bk, _run, lambda: B.partial_groupby(part, by, aggs, topk_keys)
    )


def _vc_from_raw(key_dtype, cnt_row: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    cnt = np.asarray(cnt_row)
    present = cnt > 0
    values = np.nonzero(present)[0].astype(key_dtype)
    return values, cnt[present].astype(np.int64)


def partial_value_counts(
    part: Partition, col: str, backend: Optional[str] = None
) -> Tuple[np.ndarray, np.ndarray]:
    bk = active_backend(backend)
    c = part.columns[col]
    if bk == "numpy" or c.dictionary is None or part.nrows == 0:
        return B.partial_value_counts(part, col)

    def _run():
        with obs.span("dispatch.prep"):
            keys, valid = _dev_i32(c), _dev_valid(c)
        with _call("value_counts", bk, [part]), _kernel(bk):
            _, cnts = ops.segment_reduce_batch(
                keys, [], [valid], len(c.dictionary), [], []
            )
        return _vc_from_raw(c.data.dtype, _fetch(cnts)[0])

    return _guarded(
        "value_counts", bk, _run, lambda: B.partial_value_counts(part, col)
    )


# --------------------------------------------------------------------------- #
# sort — full: exact-split lax.sort; limit: topk threshold + residual argsort  #
# --------------------------------------------------------------------------- #

TOPK_MAX_K = 128  # the kernel runs k (max, mask) rounds; beyond this, numpy


def _sort_keys(key_col: Column, ascending: bool) -> np.ndarray:
    """f64 sort keys with the numpy reference's null handling (nulls last)."""
    keys = np.asarray(key_col.data, np.float64)
    if key_col.mask is not None:
        m = np.asarray(key_col.mask)
        keys = np.where(m, keys, np.inf if ascending else -np.inf)
    return keys


def _sort_keys_exact(keys: np.ndarray) -> bool:
    """True when the 3×f32 split orders ``keys`` exactly: no unmasked NaN (no
    total order to reproduce — numpy's argsort parks them last), no finite
    magnitude that would overflow the f32 ``hi`` component to ±inf, and the
    split reconstructs every key exactly (``hi + mid + lo == x`` in f64).
    The reconstruction check catches underflow: magnitudes on or below the
    f32 subnormal grid (roughly |x| < 2^-100) lose residual bits, so distinct
    tiny keys would collapse to identical components and sort as ties."""
    if np.isnan(keys).any():
        return False
    finite = np.isfinite(keys)
    if not finite.any():
        return True
    f = keys[finite]
    if np.abs(f).max() >= np.finfo(np.float32).max:
        return False
    hi, mid, lo = ops.split_f64(f)
    recon = hi.astype(np.float64) + mid.astype(np.float64) + lo.astype(np.float64)
    return bool((recon == f).all())


def partial_sort(
    part: Partition,
    by: str,
    ascending: bool,
    limit: Optional[int],
    n_samples: int = 32,
    backend: Optional[str] = None,
) -> Tuple[Partition, np.ndarray]:
    bk = active_backend(backend)
    key_col = part.columns.get(by)
    if bk == "numpy" or key_col is None or part.nrows == 0:
        return B.partial_sort(part, by, ascending, limit, n_samples)
    if limit is None:
        return _partial_sort_full(part, key_col, by, ascending, n_samples, bk)
    return _partial_sort_limit(part, key_col, by, ascending, limit, n_samples, bk)


def _sorted_result(
    part: Partition, keys: np.ndarray, idx: np.ndarray, n_samples: int
) -> Tuple[Partition, np.ndarray]:
    sorted_part = part.take(idx)
    skeys = keys[idx]
    if len(skeys) == 0:
        samples = np.array([])
    else:
        samples = skeys[
            np.linspace(0, len(skeys) - 1, min(n_samples, len(skeys))).astype(int)
        ]
    return sorted_part, samples


def _partial_sort_full(
    part: Partition,
    key_col: Column,
    by: str,
    ascending: bool,
    n_samples: int,
    bk: str,
) -> Tuple[Partition, np.ndarray]:
    """Full (non-limit) partition sort: one jit'd multi-key ``lax.sort`` over
    the exactly-split f64 keys — bit-for-bit the numpy stable argsort,
    including null-last ordering and ties (dictionary codes sort string
    columns, since `from_pydict` dictionaries are sorted)."""
    with obs.span("dispatch.prep"):
        keys = _sort_keys(key_col, ascending)
        exact = _sort_keys_exact(keys)
    if not exact:
        return B.partial_sort(part, by, ascending, None, n_samples)

    def _run():
        with _call("sort", bk, [part]), _kernel(bk):
            order = ops.argsort_f64(keys if ascending else -keys)
        return _sorted_result(part, keys, _fetch(order), n_samples)

    return _guarded(
        "sort", bk, _run, lambda: B.partial_sort(part, by, ascending, None, n_samples)
    )


def _partial_sort_limit(
    part: Partition,
    key_col: Column,
    by: str,
    ascending: bool,
    limit: int,
    n_samples: int,
    bk: str,
) -> Tuple[Partition, np.ndarray]:
    if not (1 <= limit <= TOPK_MAX_K) or key_col.is_string or part.nrows <= limit:
        return B.partial_sort(part, by, ascending, limit, n_samples)
    with obs.span("dispatch.prep"):
        keys = _sort_keys(key_col, ascending)
        has_nan = np.isnan(keys).any()
        kf32 = keys.astype(np.float32)
    if has_nan:
        # unmasked NaN keys (e.g. a merge_groupby mean output): lax.top_k
        # treats NaN as maximal and would poison the threshold, silently
        # dropping valid rows — numpy's argsort-NaN-last semantics instead
        return B.partial_sort(part, by, ascending, limit, n_samples)

    def _run():
        with _call("topk", bk, [part]), _kernel(bk):
            winners = ops.topk_padded(kf32, limit, largest=not ascending)
        winners = _fetch(winners)
        return _limit_select(part, keys, kf32, winners, ascending, limit, n_samples)

    return _guarded(
        "topk", bk, _run, lambda: B.partial_sort(part, by, ascending, limit, n_samples)
    )


def _limit_select(
    part: Partition,
    keys: np.ndarray,
    kf32: np.ndarray,
    winners: np.ndarray,
    ascending: bool,
    limit: int,
    n_samples: int,
) -> Tuple[Partition, np.ndarray]:
    """Winner values → final limit-sort result — the shared host step of the
    batched and unbatched limit paths.  Threshold in f32 space: rounding is
    monotone, so rows whose f32 key beats the f32 k-th winner are a superset
    of the true top-k (ties included)."""
    kth = winners[-1]
    cand = np.nonzero(kf32 <= kth if ascending else kf32 >= kth)[0]
    order_local = np.argsort(keys[cand] if ascending else -keys[cand], kind="stable")
    idx = cand[order_local][:limit]
    return _sorted_result(part, keys, idx, n_samples)


def merge_sort(
    partials: Sequence[Tuple[Partition, np.ndarray]],
    by: str,
    ascending: bool,
    limit: Optional[int],
    backend: Optional[str] = None,
) -> "PTable":
    """Combine step of a full sort as a *sample sort* (paper §5.1): pick
    pivots from the partials' key samples, range-split every (already sorted)
    partition with one vectorised ``searchsorted``, then order each range with
    the same exact-split device argsort.  Ranges partition rows purely by key
    value, so equal keys never straddle a boundary and stable in-range sorting
    reproduces the global stable merge bit-for-bit — while each range sorts
    nearly-sorted runs of ~n/p rows instead of one n-row ``np.argsort``.

    Falls back to the numpy merge for limit-sorts (tiny inputs), ≤1 non-empty
    partial, or keys outside the exact-split envelope."""
    bk = active_backend(backend)
    if bk == "numpy" or limit is not None:
        return B.merge_sort(partials, by, ascending, limit)
    parts = [p for p, _ in partials if p.nrows > 0]
    if len(parts) <= 1:
        return B.merge_sort(partials, by, ascending, limit)
    keys: List[np.ndarray] = []
    for p in parts:
        k = _sort_keys(p.columns[by], ascending)
        if not _sort_keys_exact(k):
            return B.merge_sort(partials, by, ascending, limit)
        keys.append(k if ascending else -k)  # sign-adjusted: each ascending
    samples = [np.asarray(s, np.float64) for _, s in partials if len(s)]
    if not samples:
        return B.merge_sort(partials, by, ascending, limit)
    sall = np.sort(np.concatenate(samples) if ascending else -np.concatenate(samples))
    nparts = len(parts)
    pivots = sall[np.linspace(0, len(sall) - 1, nparts + 1).astype(int)[1:-1]]
    splits = [np.searchsorted(k, pivots, side="left") for k in keys]

    def _run():
        out_parts: List[Partition] = []
        for r in range(nparts):
            slices: List[Partition] = []
            skeys: List[np.ndarray] = []
            for p, k, sp in zip(parts, keys, splits):
                a = int(sp[r - 1]) if r > 0 else 0
                b = int(sp[r]) if r < nparts - 1 else p.nrows
                if b > a:
                    slices.append(p.slice(a, b))
                    skeys.append(k[a:b])
            if not slices:
                continue
            chunk = PTable(slices).concat()
            with _call("merge_sort", bk, [chunk]), _kernel(bk):
                order = ops.argsort_f64(np.concatenate(skeys))
            out_parts.append(chunk.take(_fetch(order)))
        return PTable(out_parts or [parts[0].slice(0, 0)])

    return _guarded(
        "merge_sort", bk, _run, lambda: B.merge_sort(partials, by, ascending, limit)
    )


# --------------------------------------------------------------------------- #
# join — sorted right side built once, device-resident; counting probe        #
# --------------------------------------------------------------------------- #

_JOIN_INT_EXACT = 1 << 24  # f32 integer-exact range


def _join_keys_exact(col: Column) -> bool:
    """Key columns the f32 probe compares exactly: integers within f32's
    2^24 exact range and native float32.  String keys fall back to numpy —
    dictionary codes are per-table, so cross-table equality needs the decoded
    strings.  float64 keys fall back too (fractional values may not survive
    the f32 cast).  The verdict is cached on the (immutable) Column so
    think-time re-probes skip the O(n) min/max host scan — same pattern as
    the `_dev_*` device cache."""
    cached = col.__dict__.get("_join_exact")
    if cached is not None:
        return cached
    if col.is_string:
        ok = False
    else:
        d = np.asarray(col.data)
        if d.dtype.kind in "iu":
            # range-scan valid rows only: null rows hold arbitrary payloads
            # that must not force the fallback (they never match anyway)
            d = d[np.asarray(col.valid_mask())]
            ok = d.size == 0 or bool(
                int(d.min()) > -_JOIN_INT_EXACT and int(d.max()) < _JOIN_INT_EXACT
            )
        else:
            ok = d.dtype == np.float32
    col.__dict__["_join_exact"] = ok
    return ok


def _join_build_cached(right: "PTable", on: str):
    """Build phase, cached on the (immutable) right PTable: merge + sort +
    uniqueness check once, plus the padded f32 device copy of the sorted keys
    — the broadcast side stays device-resident across every left partition
    and every think-time re-probe.  ``None`` marks a right side whose keys
    the kernel cannot compare exactly (callers fall back to numpy)."""
    cache = right.__dict__.setdefault("_join_build", {})
    if on in cache:
        return cache[on]
    rmerged, r_sorted, r_order = B.join_build(right, on)
    if not _join_keys_exact(rmerged.columns[on]):
        entry = None
    else:
        entry = (rmerged, r_sorted, r_order, ops.upload(r_sorted, np.float32))
    cache[on] = entry
    return entry


def join_partition(
    left: Partition,
    right: "PTable",
    on: str,
    how: str = "inner",
    backend: Optional[str] = None,
) -> Partition:
    bk = active_backend(backend)
    lcol = left.columns.get(on)
    eligible = (
        how in ("inner", "left")
        and lcol is not None
        and left.nrows > 0
        and _join_keys_exact(lcol)
    )
    if eligible:
        # the sharded build is size/mode-gated, not backend-gated: a right
        # side too big to broadcast takes the partition-parallel path even
        # when the planner demoted the *probe* to numpy (the broadcast host
        # build is exactly the cost being avoided)
        sharded = _sharded_join_build_cached(right, on)
        if sharded is not None:
            from . import dist

            rmerged_s, sb = sharded

            def _run_sharded():
                gather, hit = dist.join_probe(sb, _fetch(_dev_f32(lcol)))
                if lcol.mask is not None:
                    hit = hit & np.asarray(lcol.mask)  # null left keys never match
                return B.join_assemble(left, rmerged_s, gather, hit, how, on)

            out = _guarded("join", "sharded", _run_sharded, lambda: None)
            if out is not None:
                return out
    if bk == "numpy" or not eligible:
        return B.join_partition(left, right, on, how)
    build = _join_build_cached(right, on)
    if build is None:
        return B.join_partition(left, right, on, how)
    rmerged, r_sorted, r_order, r_dev = build
    if len(r_sorted) == 0:
        hit = np.zeros(left.nrows, dtype=bool)
        gather = np.zeros(left.nrows, dtype=np.intp)
        if lcol.mask is not None:
            hit = hit & np.asarray(lcol.mask)
        return B.join_assemble(left, rmerged, gather, hit, how, on)

    def _run():
        with obs.span("dispatch.prep"):
            lkeys = _dev_f32(lcol)
        with _call("join", bk, [left]), _kernel(bk):
            pos, hit_dev = ops.join_probe_padded(r_dev, lkeys)
        pos, hit = _fetch((pos, hit_dev))
        gather = r_order[pos]
        if lcol.mask is not None:
            hit = hit & np.asarray(lcol.mask)  # null left keys never match
        return B.join_assemble(left, rmerged, gather, hit, how, on)

    return _guarded(
        "join", bk, _run, lambda: B.join_partition(left, right, on, how)
    )


# --------------------------------------------------------------------------- #
# predicate compaction — filter_compact                                        #
# --------------------------------------------------------------------------- #


def _compact_lossless(c: Column) -> bool:
    """Only dtypes the f32 compaction kernel moves exactly: float32 itself,
    and dictionary codes (int32 bounded by the dictionary length, far below
    f32's 2^24 integer range).  Everything else — float64, int64, plain ints —
    would be silently rounded through the kernel's f32 datapath, so it takes
    the numpy gather instead."""
    if c.data.dtype == np.float32:
        return True
    if c.dictionary is not None and len(c.dictionary) < (1 << 24):
        return True
    return False


# --------------------------------------------------------------------------- #
# fused multi-partition batch plans                                            #
#                                                                              #
# Each planner inspects a group of partitions (same shape bucket — the caller  #
# groups by `ops.pad_len`) and returns a two-phase ``(dispatch, finalize)``    #
# pair for the executor's UnitBatch, or ``None`` when any partition falls      #
# outside the kernel envelope (the caller then runs those units one at a       #
# time through the ordinary per-partition paths).  ``dispatch()`` launches     #
# ONE fused kernel for the whole group and returns without blocking (JAX       #
# async dispatch); ``finalize(handle)`` blocks, pulls results to host, and     #
# reuses the *same* postprocessing helpers as the unbatched paths — batched    #
# results are bit-for-bit identical by construction.                           #
# --------------------------------------------------------------------------- #

BatchPlan = Tuple[Any, Any]  # (dispatch: () -> handle, finalize: handle -> list)


def shape_bucket(part: Partition) -> int:
    """The jit shape bucket a partition pads to (runtime groups batches by it)."""
    return ops.pad_len(part.nrows)


def _same_bucket(parts: Sequence[Partition]) -> bool:
    return len({ops.pad_len(p.nrows) for p in parts}) == 1


def plan_stats_batch(
    parts: Sequence[Partition],
    cols: Optional[Sequence[str]] = None,
    backend: Optional[str] = None,
) -> Optional[BatchPlan]:
    bk = active_backend(backend)
    if bk == "numpy" or not parts or not _same_bucket(parts):
        return None
    if not _BOARD.is_closed("stats", bk):
        return None  # units fall back one at a time through _guarded
    names = list(cols) if cols is not None else B.numeric_columns(parts[0])
    if not names:
        return None
    for p in parts:
        p_names = list(cols) if cols is not None else B.numeric_columns(p)
        if p_names != names or p.nrows == 0:
            return None
    C = len(names)

    def dispatch():
        with _breaker_watch("stats", bk):
            with obs.span("dispatch.prep"):
                stacks = [_dev_stats_stack(p, names) for p in parts]
            with _call("stats", bk, parts), _kernel(bk):
                return ops.masked_stats_batch_parts(
                    [xs for xs, _ in stacks], [ms for _, ms in stacks]
                )

    def finalize(raw):
        raw = _readback(raw, np.float64)
        return [
            _stats_from_raw(names, raw[i * C:(i + 1) * C])
            for i in range(len(parts))
        ]

    return dispatch, finalize


def plan_groupby_batch(
    parts: Sequence[Partition],
    by: str,
    aggs: Sequence[Tuple[str, str, Any]],
    topk_keys: Optional[int] = None,
    backend: Optional[str] = None,
) -> Optional[BatchPlan]:
    bk = active_backend(backend)
    if bk == "numpy" or not parts or not _same_bucket(parts):
        return None
    if not _BOARD.is_closed("groupby", bk):
        return None
    if any(not _groupby_supported(p, by, aggs, topk_keys) for p in parts):
        return None
    nb = len(parts[0].columns[by].dictionary)
    with obs.span("dispatch.prep"):
        plans = [_groupby_plan(p, by, aggs) for p in parts]
    _, _, valids0, modes0, vidx0, aplan0 = plans[0]
    for pl in plans[1:]:
        # the fused call shares one (modes, valid_idx) trace: partitions whose
        # mask layout differs (e.g. only some have nulls in an agg column)
        # get different plan structures and cannot ride the same dispatch
        if pl[3] != modes0 or pl[4] != vidx0 or len(pl[2]) != len(valids0):
            return None
        if [(n, f, s, v) for n, f, s, v in pl[5]] != aplan0:
            return None

    def dispatch():
        with _breaker_watch("groupby", bk):
            with _call("groupby", bk, parts), _kernel(bk):
                return ops.segment_reduce_batch_parts(
                    [pl[0] for pl in plans],
                    [pl[1] for pl in plans],
                    [pl[2] for pl in plans],
                    nb, modes0, vidx0,
                )

    def finalize(handle):
        reds, cnts = _readback(tuple(handle))
        return [
            _groupby_from_raw(
                parts[i].columns[by].data.dtype, plans[i][5], reds[i], cnts[i]
            )
            for i in range(len(parts))
        ]

    return dispatch, finalize


def plan_value_counts_batch(
    parts: Sequence[Partition], col: str, backend: Optional[str] = None
) -> Optional[BatchPlan]:
    bk = active_backend(backend)
    if bk == "numpy" or not parts or not _same_bucket(parts):
        return None
    if not _BOARD.is_closed("value_counts", bk):
        return None
    if any(p.columns[col].dictionary is None or p.nrows == 0 for p in parts):
        return None
    nb = len(parts[0].columns[col].dictionary)

    def dispatch():
        with _breaker_watch("value_counts", bk):
            with obs.span("dispatch.prep"):
                keys = [_dev_i32(p.columns[col]) for p in parts]
                valids = [[_dev_valid(p.columns[col])] for p in parts]
            with _call("value_counts", bk, parts), _kernel(bk):
                return ops.segment_reduce_batch_parts(
                    keys, [[] for _ in parts], valids, nb, [], [],
                )

    def finalize(handle):
        cnts = _readback(handle[1])
        return [
            _vc_from_raw(parts[i].columns[col].data.dtype, cnts[i][0])
            for i in range(len(parts))
        ]

    return dispatch, finalize


def plan_sort_batch(
    parts: Sequence[Partition],
    by: str,
    ascending: bool,
    limit: Optional[int],
    n_samples: int = 32,
    backend: Optional[str] = None,
) -> Optional[BatchPlan]:
    bk = active_backend(backend)
    if bk == "numpy" or not parts or not _same_bucket(parts):
        return None
    if any(p.columns.get(by) is None or p.nrows == 0 for p in parts):
        return None
    if limit is None:
        if not _BOARD.is_closed("sort", bk):
            return None
        with obs.span("dispatch.prep"):
            keys_list = [_sort_keys(p.columns[by], ascending) for p in parts]
            exact = all(_sort_keys_exact(k) for k in keys_list)
        if not exact:
            return None

        def dispatch():
            with _breaker_watch("sort", bk):
                with _call("sort", bk, parts), _kernel(bk):
                    return ops.argsort_f64_parts(
                        [k if ascending else -k for k in keys_list]
                    )

        def finalize(handle):
            orders = _readback(handle)
            return [
                _sorted_result(
                    parts[i], keys_list[i], orders[i][: parts[i].nrows], n_samples
                )
                for i in range(len(parts))
            ]

        return dispatch, finalize

    if not (1 <= limit <= TOPK_MAX_K):
        return None
    if not _BOARD.is_closed("topk", bk):
        return None
    if any(
        p.columns[by].is_string or p.nrows <= limit for p in parts
    ):
        return None
    with obs.span("dispatch.prep"):
        keys_list = [_sort_keys(p.columns[by], ascending) for p in parts]
        has_nan = any(np.isnan(k).any() for k in keys_list)
        kf32s = [k.astype(np.float32) for k in keys_list]
    if has_nan:
        return None  # NaN keys poison lax.top_k thresholds (see unbatched path)

    def dispatch():
        with _breaker_watch("topk", bk):
            with _call("topk", bk, parts), _kernel(bk):
                return ops.topk_padded_parts(kf32s, limit, largest=not ascending)

    def finalize(handle):
        winners = _readback(handle)
        return [
            _limit_select(
                parts[i], keys_list[i], kf32s[i], winners[i],
                ascending, limit, n_samples,
            )
            for i in range(len(parts))
        ]

    return dispatch, finalize


def plan_select_rows_batch(
    parts: Sequence[Partition],
    keeps_fn,
    backend: Optional[str] = None,
) -> Optional[BatchPlan]:
    """Fused filter compaction over a partition group.  ``keeps_fn()`` is
    called at *dispatch* time and must return one boolean keep mask per
    partition — predicate evaluation is part of the unit's work and stays
    inside the preemption quantum."""
    bk = active_backend(backend)
    if bk == "numpy" or not parts or not _same_bucket(parts):
        return None
    if not _BOARD.is_closed("filter", bk):
        return None
    if any(p.nrows == 0 for p in parts):
        return None

    def dispatch():
        with _breaker_watch("filter", bk):
            with obs.span("dispatch.prep"):
                keeps = [np.asarray(k, bool) for k in keeps_fn()]
                xs_rows: list = []
                keeps_rows: list = []
                row_of: Dict[Tuple[int, str, str], int] = {}
                for i, (p, keep) in enumerate(zip(parts, keeps)):
                    keep_dev = ops.upload(keep)
                    for name in p.order:
                        c = p.columns[name]
                        if not _compact_lossless(c):
                            continue
                        row_of[(i, name, "data")] = len(xs_rows)
                        xs_rows.append(_dev_f32(c))
                        keeps_rows.append(keep_dev)
                        if c.mask is not None:
                            row_of[(i, name, "mask")] = len(xs_rows)
                            xs_rows.append(ops.upload(c.mask).astype(jnp.float32))
                            keeps_rows.append(keep_dev)
            out = None
            if xs_rows:
                with _call("filter", bk, parts), _kernel(bk):
                    out, _ = ops.filter_compact_padded_parts(xs_rows, keeps_rows)
            return keeps, row_of, out

    def finalize(handle):
        keeps, row_of, out = handle
        out = _readback(out) if out is not None else None
        results = []
        for i, p in enumerate(parts):
            keep = keeps[i]
            count = int(keep.sum())
            new_cols: Dict[str, Column] = {}
            for name in p.order:
                c = p.columns[name]
                drow = row_of.get((i, name, "data"))
                if drow is None:
                    new_cols[name] = c.select(keep)
                    continue
                data = out[drow][:count].astype(c.data.dtype)
                mask = None
                if c.mask is not None:
                    mask = out[row_of[(i, name, "mask")]][:count] > 0.5
                new_cols[name] = Column(data=data, mask=mask, dictionary=c.dictionary)
            results.append(Partition(new_cols, list(p.order)))
        return results

    return dispatch, finalize


def select_rows(
    part: Partition, keep: np.ndarray, backend: Optional[str] = None
) -> Partition:
    bk = active_backend(backend)
    keep = np.asarray(keep, bool)
    if bk == "numpy" or part.nrows == 0:
        return part.select_rows(keep)

    def _run():
        count = int(keep.sum())
        # upload + pad the keep mask once; column data rides the device cache
        nb = ops.pad_len(part.nrows)
        keep_dev = ops.upload(keep)
        if nb != part.nrows:
            with obs.span("dispatch.prep"):
                keep_dev = jnp.pad(
                    keep_dev, (0, nb - part.nrows), constant_values=False
                )
        new_cols: Dict[str, Column] = {}
        with _kernel(bk):
            for name in part.order:
                c = part.columns[name]
                if not _compact_lossless(c):
                    with obs.span("dispatch.prep"):
                        new_cols[name] = c.select(keep)
                    continue
                x = _dev_f32(c)
                with _call("filter", bk, [part]):
                    out, _ = ops.filter_compact_padded(x, keep_dev)
                data = _fetch(out)[:count].astype(c.data.dtype)
                mask = None
                if c.mask is not None:
                    with obs.span("dispatch.prep"):
                        m = ops.upload(c.mask).astype(jnp.float32)
                    with _call("filter", bk, [part]):
                        mout, _ = ops.filter_compact_padded(m, keep_dev)
                    mask = _fetch(mout)[:count] > 0.5
                new_cols[name] = Column(data=data, mask=mask, dictionary=c.dictionary)
        return Partition(new_cols, list(part.order))

    return _guarded("filter", bk, _run, lambda: part.select_rows(keep))


# --------------------------------------------------------------------------- #
# Fused composites: filter→reduce chains as ONE guarded kernel dispatch        #
#                                                                              #
# Partition-level entry points for the planner's fusion path                   #
# (``FrameRuntime``'s try_fused hooks): each takes the UNFILTERED partition    #
# plus the host-evaluated keep mask and runs compact+reduce inside a single    #
# jit (kernels.ops.filter_then_*), skipping the intermediate filtered          #
# partition entirely.  Each returns ``None`` when fusion is not eligible for   #
# this partition — the caller then falls back to the unfused two-dispatch      #
# sequence, so every gate here mirrors the corresponding unfused gate and the  #
# fused result is equal (to signed zero) to the unfused one by construction    #
# (see the parity contract in kernels/ops.py and tests/test_fused.py).         #
#                                                                              #
# Zero kept rows always declines: the numpy reference owns the empty-          #
# partition semantics on the unfused path, and parity is trivial there.        #
# --------------------------------------------------------------------------- #


def fused_stats_partition(
    part: Partition,
    keep: np.ndarray,
    cols: Optional[Sequence[str]] = None,
    backend: Optional[str] = None,
) -> Optional[Dict[str, ColStats]]:
    """Fused filter→describe partial: masked stats over the kept rows only."""
    bk = active_backend(backend)
    names = list(cols) if cols is not None else B.numeric_columns(part)
    if bk == "numpy" or not names or part.nrows == 0:
        return None
    keep = np.asarray(keep, bool)
    if not keep.any():
        return None

    def _run():
        with obs.span("dispatch.prep"):
            xs, ms = _dev_stats_stack(part, names)
        with _call("fused_stats", bk, [part]), _kernel(bk):
            raw = ops.filter_then_masked_stats(xs, ms, keep)
        return _stats_from_raw(names, _fetch(raw, np.float64))

    return _guarded("fused_stats", bk, _run, lambda: None)


def _fused_groupby_plan(part: Partition, by: str, aggs) -> tuple:
    """``_groupby_plan`` twin for the fused filter→groupby path: validity
    rows dedup by agg column *name* instead of mask identity.  Filtering
    materialises a fresh mask array per column, so on the filtered partition
    two aggs share a validity row exactly when they read the same column —
    deduping the parent's plan by name reproduces that structure (same
    modes / valid_idx / per-agg rows), which keeps the fused kernel's plan
    identical to the one the unfused sequence would run."""
    key_col = part.columns[by]
    kvalid = _dev_valid(key_col)
    values: list = []
    modes: list = []
    valid_idx: list = []
    valids: list = [kvalid]  # row 0: key presence
    valid_row_of: Dict[str, int] = {}
    agg_plan: list = []  # (out_name, fn, value_row | None, valid_row)
    for out_name, col, fn in aggs:
        vcol = part.columns[col]
        if vcol.mask is None:
            vrow = 0
        else:
            vrow = valid_row_of.get(col)
            if vrow is None:
                vrow = len(valids)
                valids.append(kvalid & _dev_valid(vcol))
                valid_row_of[col] = vrow
        if fn == "count":
            agg_plan.append((out_name, fn, None, vrow))
            continue
        values.append(_dev_f32(vcol))
        modes.append(_SEG_MODE[fn])
        valid_idx.append(vrow)
        agg_plan.append((out_name, fn, len(values) - 1, vrow))
    return _dev_i32(key_col), values, valids, modes, valid_idx, agg_plan


def fused_groupby_partition(
    part: Partition,
    keep: np.ndarray,
    by: str,
    aggs: Sequence[Tuple[str, str, Any]],
    topk_keys: Optional[int] = None,
    backend: Optional[str] = None,
) -> Optional[dict]:
    """Fused filter→groupby partial: segment reductions over kept rows."""
    bk = active_backend(backend)
    if bk == "numpy" or not _groupby_supported(part, by, aggs, topk_keys):
        return None
    key_col = part.columns[by]
    nb = len(key_col.dictionary)
    if nb >= 1 << 24:
        return None  # group codes ride the fused kernel's f32 compaction
    keep = np.asarray(keep, bool)
    if not keep.any():
        return None

    def _run():
        with obs.span("dispatch.prep"):
            keys, values, valids, modes, valid_idx, agg_plan = _fused_groupby_plan(
                part, by, aggs
            )
        with _call("fused_groupby", bk, [part]), _kernel(bk):
            reds, cnts = ops.filter_then_segment_reduce(
                keys, values, valids, keep, nb, modes, valid_idx
            )
        reds, cnts = _fetch((reds, cnts))
        return _groupby_from_raw(key_col.data.dtype, agg_plan, reds, cnts)

    return _guarded("fused_groupby", bk, _run, lambda: None)


def fused_topk_partition(
    part: Partition,
    keep: np.ndarray,
    by: str,
    ascending: bool,
    limit: Optional[int],
    n_samples: int = 32,
    backend: Optional[str] = None,
) -> Optional[Tuple[Partition, np.ndarray]]:
    """Fused filter→topk partial: winners from the masked parent keys, final
    rows gathered straight from the parent partition (identical math to
    ``_limit_select``, expressed in kept-row coordinates)."""
    bk = active_backend(backend)
    key_col = part.columns.get(by)
    if bk == "numpy" or key_col is None or limit is None or part.nrows == 0:
        return None
    if not (1 <= limit <= TOPK_MAX_K) or key_col.is_string:
        return None
    keep = np.asarray(keep, bool)
    kept_idx = np.nonzero(keep)[0]
    if len(kept_idx) <= limit:
        return None  # the unfused path host-sorts this tiny case anyway
    keys = _sort_keys(key_col, ascending)  # parent-row key space
    kkeys = keys[kept_idx]
    if np.isnan(kkeys).any():
        return None  # NaN poisons the top_k threshold (see _partial_sort_limit)
    kf32 = keys.astype(np.float32)

    def _run():
        with _call("fused_topk", bk, [part]), _kernel(bk):
            winners = ops.topk_masked_padded(kf32, keep, limit, largest=not ascending)
        winners = _fetch(winners)
        kth = winners[-1]
        kk32 = kf32[kept_idx]
        cand = np.nonzero(kk32 <= kth if ascending else kk32 >= kth)[0]
        order_local = np.argsort(
            kkeys[cand] if ascending else -kkeys[cand], kind="stable"
        )
        idx_local = cand[order_local][:limit]
        sorted_part = part.take(kept_idx[idx_local])
        skeys = kkeys[idx_local]
        if len(skeys) == 0:
            samples = np.array([])
        else:
            samples = skeys[
                np.linspace(0, len(skeys) - 1, min(n_samples, len(skeys))).astype(int)
            ]
        return sorted_part, samples

    return _guarded("fused_topk", bk, _run, lambda: None)


# --------------------------------------------------------------------------- #
# sharded (data-mesh) dispatch paths                                           #
#                                                                              #
# Whole-node entry points over the ``data`` mesh (frame/dist.py): ONE          #
# shard_map covers every partition of the node and the combine runs as         #
# collectives inside the jit, replacing P per-partition dispatches + the       #
# host-side merge loop.  Each returns None when it declines (no mesh, op       #
# outside the envelope) — callers fall through to the ordinary paths.          #
# "sharded" is a breaker/cost-model backend key only; it never flows through   #
# the BACKENDS policy chain (resolve() would reject it).                       #
# --------------------------------------------------------------------------- #

# Right sides whose key array exceeds this broadcast to every probe as a
# device-resident array just fine; above it, the partition-parallel build
# shards the sort across ``data`` and probes locally (env-tunable so tests
# and benches can exercise the sharded build without gigabyte tables).
JOIN_BROADCAST_MAX_BYTES = int(
    os.environ.get("REPRO_JOIN_BROADCAST_MAX", 8 << 20)
)


def sharded_available() -> bool:
    from . import dist

    return dist.sharded_available()


def sharded_stats(table: "PTable", cols: Optional[Sequence[str]] = None):
    """Merged ColStats for the table's numeric columns from ONE sharded
    dispatch of per-partition raws, merged on the host by ``B.merge_stats``
    — bit-for-bit the host path over per-partition XLA partials.  Returns
    ``None`` when declined (no mesh, <2 partitions, no numeric columns)."""
    from . import dist

    if not dist.sharded_available() or len(table.partitions) < 2:
        return None
    names = list(cols) if cols is not None else B.numeric_columns(
        table.partitions[0]
    )
    if not names:
        return None
    raws = sharded_stats_raws(table, names)
    if raws is None:
        return None
    return B.merge_stats([
        _stats_from_raw(names, np.asarray(raws[i], np.float64))
        for i in range(len(table.partitions))
    ])


def sharded_stats_raws(table: "PTable", names: Sequence[str]):
    """Per-partition (count, sum, m2, min, max) raws for EVERY partition in
    one dispatch — the sharded UnitBatch's kernel.  Row i sliced through
    ``_stats_from_raw`` is bit-identical to ``partial_stats(partitions[i])``.
    Cached on the table: think-time batches after the first are host-only."""
    from . import dist

    if not dist.sharded_available():
        return None
    key = tuple(names)
    cached = table.__dict__.get("_sharded_raws")
    if cached is not None and cached[0] == key:
        return cached[1]
    st = dist.ShardedPTable.from_table(table, key)
    if st is None:
        return None

    def _run():
        return dist.stats_raws(st)

    raw = _guarded("stats", "sharded", _run, lambda: None)
    if raw is not None:
        table.__dict__["_sharded_raws"] = (key, raw)
    return raw


def _shared_dictionary(table: "PTable", col: str):
    """The column's dictionary when every partition shares the same object
    (from_pydict encodes once, so derived tables keep sharing); None otherwise
    — cross-partition codes are only comparable against one dictionary."""
    d0 = table.partitions[0].columns[col].dictionary
    if d0 is None:
        return None
    for p in table.partitions[1:]:
        c = p.columns.get(col)
        if c is None or c.dictionary is not d0:
            return None
    return d0


def _sharded_seg_plan(part: Partition, by: str, aggs):
    """Host-side mirror of ``_groupby_plan`` (same structure, numpy rows for
    stacking instead of per-column device uploads)."""
    key_col = part.columns[by]
    kvalid = np.asarray(key_col.valid_mask())
    values: list = []
    modes: list = []
    valid_idx: list = []
    valids: list = [kvalid]
    valid_row_of: Dict[int, int] = {}
    agg_plan: list = []
    for out_name, col, fn in aggs:
        vcol = part.columns[col]
        if vcol.mask is None:
            vrow = 0
        else:
            k = id(vcol.mask)
            vrow = valid_row_of.get(k)
            if vrow is None:
                vrow = len(valids)
                valids.append(kvalid & np.asarray(vcol.mask))
                valid_row_of[k] = vrow
        if fn == "count":
            agg_plan.append((out_name, fn, None, vrow))
            continue
        values.append(np.asarray(vcol.data, np.float32))
        modes.append(_SEG_MODE[fn])
        valid_idx.append(vrow)
        agg_plan.append((out_name, fn, len(values) - 1, vrow))
    return (
        np.asarray(key_col.data, np.int32),
        values, valids, tuple(modes), tuple(valid_idx), agg_plan,
    )


def _sharded_seg_stack(table: "PTable", by: str, aggs, cache_key):
    """Stacked (keys, values, valids) device matrices for a whole-table
    segment reduction, plus the shared plan.  None when the plan structure
    differs across partitions (mask layout drift) — the per-partition path
    handles those."""
    from . import dist

    mesh = dist.data_mesh()
    if mesh is None:
        return None
    cached = table.__dict__.get("_sharded_seg")
    if cached is not None and cached[0] == cache_key:
        return cached[1]
    parts = table.partitions
    plans = [_sharded_seg_plan(p, by, aggs) for p in parts]
    k0, v0, m0, modes0, vidx0, plan0 = plans[0]
    for pl_ in plans[1:]:
        if (
            pl_[3] != modes0
            or pl_[4] != vidx0
            or len(pl_[2]) != len(m0)
            or [(a, f, s, v) for a, f, s, v in pl_[5]]
            != [(a, f, s, v) for a, f, s, v in plan0]
        ):
            return None
    ppad, pl, d = dist._padded_layout(len(parts), mesh)
    nb = dist._common_bucket([p.nrows for p in parts])
    S, V = len(v0), len(m0)
    keys = np.zeros((ppad, nb), np.int32)
    values = np.zeros((ppad, S, nb), np.float32)
    valids = np.zeros((ppad, V, nb), bool)
    for i, (k, vs, ms, _, _, _) in enumerate(plans):
        n = len(k)
        keys[i, :n] = k
        for s in range(S):
            values[i, s, :n] = vs[s]
        for v in range(V):
            valids[i, v, :n] = ms[v]
    entry = (
        dist.put_sharded(mesh, keys),
        dist.put_sharded(mesh, values),
        dist.put_sharded(mesh, valids),
        modes0, vidx0, plan0, pl, d,
    )
    table.__dict__["_sharded_seg"] = (cache_key, entry)
    return entry


def sharded_value_counts(table: "PTable", col: str):
    """One collective dispatch for a whole-table value_counts over a
    dictionary column: per-partition count rows + exact integer psum.
    Returns ONE (values, counts) partial — feed ``B.merge_value_counts``."""
    from . import dist

    if not dist.sharded_available() or len(table.partitions) < 2:
        return None
    c0 = table.partitions[0].columns.get(col)
    if c0 is None:
        return None
    dictionary = _shared_dictionary(table, col)
    if dictionary is None:
        return None
    stack = _sharded_seg_stack(table, col, (), ("vc", col))
    if stack is None:
        return None
    keys, values, valids, modes, vidx, _, pl, d = stack

    def _run():
        _, cnts = dist.segment_fold(
            dist.data_mesh(), keys, values, valids,
            len(dictionary), modes, vidx, pl, d,
        )
        return _vc_from_raw(c0.data.dtype, cnts[0])

    return _guarded("value_counts", "sharded", _run, lambda: None)


def sharded_groupby(table: "PTable", by: str, aggs):
    """One collective dispatch for a whole-table groupby: per-partition
    segment reductions + an in-jit f64 fold in global partition order (the
    host combine is a flat left fold — np.add.at over payloads in partition
    order — replayed exactly).  Returns ONE partial dict — feed
    ``B.merge_groupby``."""
    from . import dist

    if not dist.sharded_available() or len(table.partitions) < 2:
        return None
    parts = table.partitions
    for p in parts:
        if not _groupby_supported(p, by, aggs, None):
            return None
    dictionary = _shared_dictionary(table, by)
    if dictionary is None or len(dictionary) >= 1 << 24:
        return None
    stack = _sharded_seg_stack(table, by, tuple(aggs), ("gb", by, tuple(aggs)))
    if stack is None:
        return None
    keys, values, valids, modes, vidx, agg_plan, pl, d = stack
    key_dtype = parts[0].columns[by].data.dtype

    def _run():
        reds, cnts = dist.segment_fold(
            dist.data_mesh(), keys, values, valids,
            len(dictionary), modes, vidx, pl, d,
        )
        return _groupby_from_raw(key_dtype, agg_plan, reds, cnts)

    return _guarded("groupby", "sharded", _run, lambda: None)


def sharded_topk(
    table: "PTable", by: str, ascending: bool, limit: int, n_samples: int = 32
):
    """One collective dispatch for every partition's top-k winners, then the
    same host candidate selection (``_limit_select``) the per-partition path
    runs — partials are bit-identical to it.  Partitions outside the kernel
    envelope (≤ limit rows, NaN keys) take the numpy partial individually,
    exactly as the host path would.  Returns the (partition, samples) partial
    list — feed ``B.merge_sort``."""
    from . import dist

    if not dist.sharded_available() or len(table.partitions) < 2:
        return None
    if not (1 <= limit <= TOPK_MAX_K):
        return None
    parts = table.partitions
    for p in parts:
        c = p.columns.get(by)
        if c is None or c.is_string:
            return None
    mesh = dist.data_mesh()
    cached = table.__dict__.get("_sharded_topk")
    tkey = (by, ascending)
    if cached is not None and cached[0] == tkey:
        kf64s, kf32s, stack, pl = cached[1]
    else:
        ppad, pl, d = dist._padded_layout(len(parts), mesh)
        nb = dist._common_bucket([p.nrows for p in parts])
        sentinel = np.float32(np.inf if ascending else -np.inf)
        kf64s = [_sort_keys(p.columns[by], ascending) for p in parts]
        kf32s = [k.astype(np.float32) for k in kf64s]
        host = np.full((ppad, nb), sentinel, np.float32)
        for i, k in enumerate(kf32s):
            host[i, : len(k)] = k
        stack = dist.put_sharded(mesh, host)
        table.__dict__["_sharded_topk"] = (tkey, (kf64s, kf32s, stack, pl))

    def _run():
        winners = dist.topk_winners(mesh, stack, limit, not ascending, pl)
        out = []
        for i, part in enumerate(parts):
            if part.nrows <= limit or np.isnan(kf64s[i]).any():
                out.append(B.partial_sort(part, by, ascending, limit, n_samples))
            else:
                out.append(
                    _limit_select(
                        part, kf64s[i], kf32s[i], winners[i],
                        ascending, limit, n_samples,
                    )
                )
        return out

    return _guarded("topk", "sharded", _run, lambda: None)


def plan_stats_sharded_batch(table: "PTable", indices: Sequence[int]):
    """Sharded :class:`UnitBatch` plan for the stats family: ONE collective
    dispatch produces every partition's (count, sum, m2, min, max) raw row,
    and ``finalize`` slices the listed slots through ``_stats_from_raw`` —
    each slot bit-identical to ``partial_stats`` of that partition.  Returns
    ``(dispatch, finalize, n_devices)`` or ``None`` when the table is outside
    the sharded envelope."""
    from . import dist

    if not dist.sharded_available() or len(table.partitions) < 2:
        return None
    names = tuple(B.numeric_columns(table.partitions[0]))
    if not names or dist.ShardedPTable.from_table(table, names) is None:
        return None

    def dispatch():
        return sharded_stats_raws(table, names)

    def finalize(raws):
        if raws is None:  # collective declined at run time: host per-unit path
            return [partial_stats(table.partitions[i]) for i in indices]
        return [
            _stats_from_raw(names, np.asarray(raws[i], np.float64))
            for i in indices
        ]

    return dispatch, finalize, dist.device_count()


def _sharded_join_build_cached(right: "PTable", on: str):
    """Partition-parallel build, cached on the right table: shard the (key,
    row-id) pairs across ``data`` and sort each shard on its own device —
    for right sides whose broadcast key array would exceed
    ``JOIN_BROADCAST_MAX_BYTES`` (or when sharding is forced on).  ``None``
    marks a right side outside the envelope; the broadcast path covers it."""
    from . import dist

    cache = right.__dict__.setdefault("_sharded_join", {})
    if on in cache:
        return cache[on]
    entry = None
    total = sum(p.nrows for p in right.partitions)
    if (
        dist.sharded_available()
        and total > 0
        and (total * 4 > JOIN_BROADCAST_MAX_BYTES or dist.mode() == "on")
    ):
        rmerged = right.concat()
        rcol = rmerged.columns.get(on)
        if rcol is not None and not rcol.is_string and _join_keys_exact(rcol):
            keys = np.asarray(rcol.data, np.float32)
            valid = np.asarray(rcol.valid_mask())
            if np.isfinite(keys[valid]).all():
                kf = np.where(valid, keys, np.float32(np.inf)).astype(np.float32)
                ids = np.where(
                    valid, np.arange(len(kf), dtype=np.int32), np.int32(-1)
                ).astype(np.int32)
                # duplicate valid keys raise here, same error as join_build
                entry = (rmerged, dist.join_build(kf, ids))
    cache[on] = entry
    return entry
