"""One run of one cell: set-up, warm-up, the measured window, the check.

The window drives the program's serving entry point, ``MultiTenantServer``
(``serve/multitenant.py``), on one real-clock ``Engine`` whose background
worker harvests think time.  Each analyst is a tenant that authors its frames
in a private ``Session`` over the shared catalog, submits them (the server
interns them into the shared DAG, so equal frames of different analysts are
one node) and interacts.  One driver thread issues the interactions in the
order they fall due; an analyst's next interaction is due when its previous
one was shown plus its think time (a closed loop), and latency runs from due
to shown, so waiting behind another analyst or behind the worker's pause
counts.  A progressive interaction shows its first bounded estimate, then
upgrades to the exact result; it is shown when exact.
"""
from __future__ import annotations

import contextlib
import gc
import heapq
import importlib
import json
import shutil
import sys
import tempfile
import threading
import time
import traceback
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from . import check, tables, traffic
from . import trace as tracing
from .reference import Reference

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
CHECK_STREAM = 3


class NoChip(RuntimeError):
    """No accelerator of a known kind, or fewer chips than the cell needs."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    bench: dict

    def metric_names(self, traced: bool) -> List[str]:
        key = "per_layer" if traced else "end_to_end"
        return [m["name"] for m in self.bench[key]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / config["file"]),
        mix=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{name}.json")["limits"],
        bench=bench,
    )


@dataclass
class Shown:
    """One interaction issued in the window."""

    analyst: str
    item: traffic.Interaction
    due: float
    issued: float
    first: float  # first bounded estimate (progressive) or the exact result
    shown: float
    engine_latency_s: float
    cached: bool
    table: Optional[dict] = None
    error: Optional[str] = None

    @property
    def latency_s(self) -> float:
        return self.shown - self.due

    @property
    def queue_wait_s(self) -> float:
        return self.first - self.due - self.engine_latency_s


@dataclass
class RunRecord:
    """What a metric reader may read (``metrics/<name>.py``)."""

    cell: str
    shown: List[Shown]
    window_s: float
    units_total: int
    units_foreground: int
    served: Dict[str, int]
    window_compiles: int
    trace: Optional[tracing.TraceSummary] = None


class CompileClock:
    """Programs JAX lowers (every jit specialisation, found in the persistent
    cache or not) and compiles (cache misses), from any thread."""

    def __init__(self):
        self.lowered = 0
        self.compiled = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        jax.monitoring.unregister_event_listener(self._on_count)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration
                self.lowered += event.endswith("jaxpr_to_mlir_module_duration")
                self.compiled += event.endswith("backend_compile_duration")

    def _on_count(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


def log(tag: str, payload) -> None:
    print(f"{tag} {json.dumps(payload, default=str)}", file=sys.stderr, flush=True)


def check_devices(chips: int, peaks: dict):
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


class Driver:
    """Issues interactions through one ``MultiTenantServer``."""

    def __init__(self, catalog, engine_kwargs: dict, on_shown=None):
        from repro.frame import Session
        from repro.serve.multitenant import MultiTenantServer

        self.session = Session(catalog=catalog, mode="real", **engine_kwargs)
        self.engine = self.session.engine
        self.server = MultiTenantServer(self.engine)
        self.catalog = catalog
        self._authors: Dict[str, object] = {}
        self.units_foreground = 0
        self.on_shown = on_shown

    def load_tables(self) -> list:
        """Materialise every base table in the shared engine."""
        return [self.engine.value_of(self.session.read_table(name).node)
                for name in self.catalog.tables]

    def author(self, tenant: str):
        from repro.frame import Session

        s = self._authors.get(tenant)
        if s is None:
            s = self._authors[tenant] = Session(catalog=self.catalog, mode="sim")
        return s

    def issue(self, tenant: str, item: traffic.Interaction, due: float) -> Shown:
        from .program import as_table, build_action, build_frame

        eng = self.engine
        issued = time.perf_counter()
        shown = Shown(tenant, item, due, issued, issued, issued, 0.0, False)
        units0 = eng.executor.stats.units_run
        with jax.profiler.TraceAnnotation(f"interact:{item.template}"):
            try:
                s = self.author(tenant)
                node = build_action(build_frame(s, item.recipe), item.action)
                # MultiTenantServer.submit edits the scheduler's per-tenant
                # memo without the engine's lock, while the background
                # worker's pick fills it under that lock ("dictionary
                # changed size during iteration" in a warm-up on a TPU v5e):
                # submit under the lock, as the worker picks
                with eng._lock:
                    root = self.server.submit(tenant, [node]).roots[0]
                shown.cached = root.nid in eng.cache
                n_rec = len(eng.metrics.interactions)
                if item.progressive:
                    pr = self.server.interact(tenant, root, progressive=True)
                    shown.first = time.perf_counter()
                    value = pr.upgrade()
                else:
                    value = self.server.interact(tenant, root)
                    shown.first = time.perf_counter()
                shown.shown = time.perf_counter()
                shown.engine_latency_s = eng.metrics.interactions[n_rec].latency_s
                shown.table = as_table(value)
            except Exception as exc:  # a failed interaction is counted, not fatal
                shown.error = f"{type(exc).__name__}: {exc}"
                shown.first = shown.shown = time.perf_counter()
                traceback.print_exc(file=sys.stderr)
        self.units_foreground += eng.executor.stats.units_run - units0
        return shown

    def drive(self, analysts: List[traffic.Analyst], opened: float,
              close: float) -> List[Shown]:
        """Every interaction due before ``close``, in order of due time."""
        out: List[Shown] = []
        heap = [(opened + a.start_s, i, 0) for i, a in enumerate(analysts)]
        heapq.heapify(heap)
        while heap and heap[0][0] < close:
            due, i, j = heapq.heappop(heap)
            a = analysts[i]
            if j >= len(a.interactions):  # this analyst's script is done
                continue
            wait = due - time.perf_counter()
            if wait > 0:
                with jax.profiler.TraceAnnotation("think_wait"):
                    time.sleep(wait)
            s = self.issue(a.name, a.interactions[j], due)
            out.append(s)
            if self.on_shown is not None:
                self.on_shown(s)
            heapq.heappush(heap, (s.shown + a.thinks[j], i, j + 1))
        return out

    def stop(self) -> None:
        self.engine.stop_background()


def warm_up(catalog, cell: Cell, analysts: List[traffic.Analyst], seconds: float,
            clock: CompileClock) -> None:
    """Compile what the window will run: every interaction the window can
    issue, on this run's tables, in an engine of its own with its background
    worker running, so that the window's engine starts with nothing cached.
    The window's own interactions, because the program compiles its eager
    steps for each exact partition length a filter leaves."""
    t0 = time.perf_counter()

    def progress(s: Shown) -> None:  # where a slow warm-up spends its time
        log("warmup", {"at_s": round(time.perf_counter() - t0, 3),
                       "template": s.item.template, "s": round(s.shown - s.issued, 3),
                       "lowered": clock.lowered, "compiled": clock.compiled})

    scripts = traffic.due_within(analysts, seconds)
    replay = [traffic.Analyst(a.name, 0.0, items, [0.0] * len(items))
              for a, items in zip(analysts, scripts)]
    d = Driver(catalog, cell.config["engine"], on_shown=progress)
    d.load_tables()
    d.engine.start_background()
    shown = d.drive(replay, time.perf_counter(), float("inf"))
    d.stop()
    errors = [s.error for s in shown if s.error]
    if errors:
        raise RuntimeError(f"warm-up interactions failed: {errors[:3]}")


def sample_keys(entries, cap: int, seed: int) -> list:
    """Up to ``cap`` distinct (frame, action) pairs of ``entries`` (pairs of
    an interaction and whether it was a cache hit), drawn from the seed
    round-robin over the (template, cache hit) groups; sorted so that frames
    with a common prefix come together, as the reference's memo wants."""
    groups: Dict[tuple, list] = {}
    for item, cached in entries:
        keys = groups.setdefault((item.template, cached), [])
        if (item.recipe, item.action) not in keys:
            keys.append((item.recipe, item.action))
    rng = np.random.default_rng(
        np.random.SeedSequence(tables.seed_words(seed) + [CHECK_STREAM]))
    pools = [(list(rng.permutation(len(keys))), keys) for keys in groups.values()]
    chosen: set = set()
    while len(chosen) < cap and any(pool for pool, _ in pools):
        for pool, keys in pools:
            if pool and len(chosen) < cap:
                chosen.add(keys[pool.pop()])
    return sorted(chosen, key=lambda k: [repr(x) for x in k[0]] + [repr(k[1])])


def run_check(data, shown: List[Shown], cap: int, seed: int):
    """Numbers of the output check: every shown interaction whose (frame,
    action) is among the sampled pairs, against the reference."""
    answered = [s for s in shown if s.error is None]
    keys = sample_keys([(s.item, s.cached) for s in answered], cap, seed)
    ref = Reference(data)
    results = {key: ref.evaluate(*key) for key in keys}
    mism, worst, n = 0, 0.0, 0
    for s in answered:
        key = (s.item.recipe, s.item.action)
        if key in results:
            m, w = check.compare(s.table, results[key])
            mism, worst, n = mism + m, max(worst, w), n + 1
    unanswered = len(shown) - len(answered)
    return {"exact_mismatches": mism, "stat_rel_err": worst,
            "unanswered": unanswered}, n, len(keys)


def templates(shown: List[Shown]) -> dict:
    """Per template: count, cache hits, and the 50th, 90th percentile and
    largest latency and queue wait in seconds."""
    out: Dict[str, dict] = {}
    for s in shown:
        out.setdefault(s.item.template, []).append(s)
    def q(xs):
        return [float(np.percentile(xs, 50)), float(np.percentile(xs, 90)), max(xs)]
    return {t: {"n": len(ss), "cached": sum(s.cached for s in ss),
                "latency_s": q([s.latency_s for s in ss]),
                "queue_wait_s": q([s.queue_wait_s for s in ss])}
            for t, ss in sorted(out.items())}


def read_metric(name: str, run: RunRecord):
    return importlib.import_module(f"bench.metrics.{name.split('.')[0]}").read(run)


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks or [0]))


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float) -> dict:
    """One run; returns the result line's object."""
    devs = check_devices(cell.chips, load_json(BENCH / "peaks.json")["devices"])
    clock = CompileClock()
    try:
        return _run(cell, seed, seconds, traced, t_start, devs, clock)
    finally:
        clock.close()


def _run(cell, seed, seconds, traced, t_start, devs, clock) -> dict:
    from repro.frame import backend as BK

    from .program import BenchCatalog

    t0 = time.perf_counter()
    data = tables.make_tables(cell.config)
    catalog = BenchCatalog(data)
    t_gen = time.perf_counter()
    for table in Driver(catalog, cell.config["engine"]).load_tables():
        BK.warm_device_cache(table)  # every base column uploaded once
    t_up = time.perf_counter()
    c0 = (clock.lowered, clock.compiled, clock.seconds, clock.cache_hits)
    log("stage", {"generated_s": t_gen - t0, "uploaded_s": t_up - t_gen})
    analysts = traffic.generate(cell.mix, seed)
    warm_up(catalog, cell, analysts, seconds, clock)
    gc.collect()
    t_warm = time.perf_counter()
    setup = {
        "generate_s": t_gen - t0, "upload_s": t_up - t_gen,
        "warmup_s": t_warm - t_up,
        "warmup_compile_s": clock.seconds - c0[2],
        "warmup_lowered": clock.lowered - c0[0],
        "warmup_compiled": clock.compiled - c0[1],
        "warmup_cache_hits": clock.cache_hits - c0[3],
        "table_bytes": sum(t.nbytes for t in data.values()),
    }

    drv = Driver(catalog, cell.config["engine"])
    drv.load_tables()
    BK.reset_served_counts()
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    drv.engine.start_background()
    lowered0 = clock.lowered
    units0 = drv.engine.executor.stats.units_run
    opened = time.perf_counter()
    setup_s = opened - t_start
    setup["setup_s"] = setup_s
    print(f"setup {json.dumps(setup)}", flush=True)
    if traced:
        jax.profiler.start_trace(log_dir)
    with (jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN) if traced
          else contextlib.nullcontext()):
        shown = drv.drive(analysts, opened, opened + seconds)
    closed = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    window_compiles = clock.lowered - lowered0
    drv.stop()
    run = RunRecord(
        cell=cell.name, shown=shown, window_s=closed - opened,
        units_total=drv.engine.executor.stats.units_run - units0,
        units_foreground=drv.units_foreground,
        served={f"{op}|{bk}": n for (op, bk), n in BK.served_counts().items()},
        window_compiles=window_compiles,
    )
    memory_peak = peak_bytes(devs)
    del drv
    gc.collect()
    if traced:
        t_tr = time.perf_counter()
        run.trace = tracing.summarize(tracing.load(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        log("trace", {"read_s": time.perf_counter() - t_tr,
                      "busy_s": run.trace.busy_s, "window_s": run.trace.window_s,
                      "devices": run.trace.devices})

    t_ck = time.perf_counter()
    numbers, n_checked, n_refs = run_check(data, shown, int(cell.mix["check_sample"]), seed)
    log("check", {"seconds": time.perf_counter() - t_ck, "interactions": n_checked,
                  "distinct": n_refs})
    log("window", {"interactions": len(shown), "window_s": run.window_s,
                   "lowered": window_compiles, "served": run.served,
                   "units": run.units_total, "units_foreground": run.units_foreground})
    log("templates", templates(shown))
    log("latencies", sorted(round(s.latency_s, 4) for s in shown))

    metrics = {}
    units = {m["name"]: m["unit"] for m in cell.bench["end_to_end"] + cell.bench["per_layer"]}
    for name in cell.metric_names(traced):
        value = setup_s if name == "setup_s" else read_metric(name, run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    limits = cell.limits
    correct = bool(shown) and n_checked > 0 and check.within(numbers, limits)
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(shown),
              "failed": sum(s.error is not None for s in shown),
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    for k in limits:
        print(f"check {k} {numbers[k]!r} limit {limits[k]!r}", file=sys.stderr)
    return result
