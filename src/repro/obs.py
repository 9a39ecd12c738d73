"""The program's spans, counters and compile clock.

``span(name, **attrs)`` times one piece of work on the host.  It is always
on: entering opens a ``jax.profiler.TraceAnnotation`` (so, under a profiler
trace, the span lands in the ``.xplane.pb`` on the device ops' clock, nested
under whatever annotation the caller is in) and exiting appends one record
to a bounded in-memory ring.  Off the profiler a span costs two clock reads,
an annotation object and a deque append.

A record is ``SpanRecord(sid, parent, name, rid, thread, t0_ns, t1_ns,
self_ns, attrs)``:

* ``parent`` is the ``sid`` of the span the same thread was in (0 at the
  top), from a thread-local stack;
* ``rid`` is the request the span works for: ``Engine.display`` opens its
  span with a fresh request id, background work with ``("node", nid)``, and
  child spans inherit their parent's;
* times are ``time.perf_counter_ns()``, the clock callers stamp requests
  with, so a window of spans is a cut by time;
* ``self_ns`` is the span's time less that of its direct children;
* ``attrs`` holds a few ints (rows, bucket, bytes, node id, cache hit): the
  counters ride on the spans, so one window cut serves both.

Span names come from a fixed vocabulary (``NAMES``); the reduction of a
window into per-layer numbers lives with the benchmark.

``device_scope(name)`` names the device ops a jitted function compiles to:
it runs the traced body inside ``jax.named_scope(name)``, which puts the name
into every op's metadata and names the Pallas custom calls.

``CompileClock`` splits what JAX spends before a program runs into jaxpr
tracing, MLIR lowering and backend compilation (seconds and counts), and
counts persistent-cache hits, from any thread.
"""
from __future__ import annotations

import collections
import functools
import itertools
import threading
import time
from typing import Dict, List, NamedTuple

import jax

# Span names, by layer.
NAMES = (
    # serving front end (serve/multitenant.py)
    "serve.submit",
    # engine and cache (core/engine.py)
    "engine.display", "engine.pause_ack", "engine.lock_wait", "engine.fast_path",
    "engine.speculate",
    # executor (core/executor.py)
    "exec.node", "exec.unit", "exec.batch", "exec.combine",
    # kernel dispatch (frame/runtime.py, frame/backend.py, kernels/ops.py)
    "dispatch.partial", "dispatch.prep", "dispatch.upload", "dispatch.call",
    "dispatch.wait", "dispatch.readback",
    # background worker (core/engine.py)
    "worker.pick", "worker.fetch", "worker.execute", "worker.store",
)

# Enough for a 45 s window of one analyst's interactions (about 200 spans
# each) and the background worker's spans around them, with room to spare.
RING_SPANS = 1 << 18


class SpanRecord(NamedTuple):
    sid: int
    parent: int
    name: str
    rid: object
    thread: int
    t0_ns: int
    t1_ns: int
    self_ns: int
    attrs: Dict[str, int]

    @property
    def ns(self) -> int:
        return self.t1_ns - self.t0_ns


_RING: collections.deque = collections.deque(maxlen=RING_SPANS)
_TLS = threading.local()
_SIDS = itertools.count(1)
_RIDS = itertools.count(1)
_now = time.perf_counter_ns
_annotation = jax.profiler.TraceAnnotation


def _stack() -> list:
    try:
        return _TLS.stack
    except AttributeError:
        _TLS.stack = []
        return _TLS.stack


def new_request() -> int:
    """A fresh request id for an interaction's root span."""
    return next(_RIDS)


class span:
    """``with span("dispatch.upload", bytes=n) as s: ...``; ``s.ns`` is the
    duration once the block has left, and ``s.attrs`` may be added to inside
    it.  ``rid`` overrides the request id inherited from the parent span."""

    __slots__ = ("name", "attrs", "rid", "sid", "parent", "t0", "ns", "child_ns",
                 "_ann")

    def __init__(self, name: str, rid: object = None, **attrs: int):
        self.name = name
        self.attrs = attrs
        self.rid = rid

    def __enter__(self) -> "span":
        stack = _stack()
        parent = stack[-1] if stack else None
        self.parent = parent
        if self.rid is None and parent is not None:
            self.rid = parent.rid
        self.sid = next(_SIDS)
        self.child_ns = 0
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        stack.append(self)
        self.t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        t1 = _now()
        _TLS.stack.pop()
        self._ann.__exit__(None, None, None)
        self.ns = ns = t1 - self.t0
        parent = self.parent
        if parent is not None:
            parent.child_ns += ns
        _RING.append((self.sid, parent.sid if parent is not None else 0, self.name,
                      self.rid, threading.get_ident(), self.t0, t1,
                      ns - self.child_ns, self.attrs))


def records() -> List[SpanRecord]:
    """Every span still in the ring, oldest first."""
    return [SpanRecord._make(r) for r in list(_RING)]


def device_scope(name: str):
    """Decorator for a function that is traced into a jitted program: its
    ops carry ``name`` in their metadata (``jax.named_scope``).  Put it
    under the ``jax.jit``: a scope around a call of a compiled function does
    not reach the ops inside it."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


class CompileClock:
    """Seconds and counts of jaxpr tracing, MLIR lowering and backend
    compilation (a persistent-cache hit is a backend compile that found its
    program), and persistent-cache hits; ``close()`` stops listening."""

    PHASES = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "compile",
    }

    def __init__(self):
        self.seconds_by = {p: 0.0 for p in self.PHASES.values()}
        self.count_by = {p: 0 for p in self.PHASES.values()}
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        phase = self.PHASES.get(event)
        if phase is not None:
            with self._lock:
                self.seconds_by[phase] += duration
                self.count_by[phase] += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    @property
    def seconds(self) -> float:
        """All three phases."""
        return sum(self.seconds_by.values())

    @property
    def compiles(self) -> int:
        """Programs handed to the backend compiler (cache hits included)."""
        return self.count_by["compile"]

    def split(self) -> dict:
        with self._lock:
            out = {f"{p}_s": s for p, s in self.seconds_by.items()}
            out.update({f"{p}_n": n for p, n in self.count_by.items()})
            out["cache_hits"] = self.cache_hits
        return out


def split_since(clock: CompileClock, before: dict) -> dict:
    """``clock.split()`` less an earlier ``split()``."""
    return {k: v - before[k] for k, v in clock.split().items()}
