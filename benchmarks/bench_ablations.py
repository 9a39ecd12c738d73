"""Ablations of the paper's §5 mechanisms (EXPERIMENTS.md §Ablations).

* scheduler policies   — Eq 1 utility vs Eq 4 vs FIFO/LIFO/random/cheapest
* cache eviction       — paper Eq 3 verbatim vs corrected vs LRU vs size-only
* partitioning         — think-time-aware (paper §5.1) vs fixed coarse/fine
* speculation          — filter-literal-tweaking workload, on vs off
* opportunistic serving— anticipated-prompt prefill warming (beyond-paper)
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List

import numpy as np

sys.path.insert(0, "src")

from repro.core import ThinkTimeModel  # noqa: E402
from repro.frame import Catalog, ColSpec, Session, TableSpec  # noqa: E402
from repro.frame.partitioner import uniform_partitions  # noqa: E402

from .workloads import make_catalog, run_notebook  # noqa: E402

N_NOTEBOOKS = 3


def _mean_latency(policy: str, predictor=None, seeds=range(N_NOTEBOOKS)) -> float:
    lats = []
    for i in seeds:
        cat = make_catalog(seed=0)
        s = Session(catalog=cat, mode="sim", policy=policy, predictor=predictor)
        # tight think budget: scheduling ORDER decides what gets prewarmed
        run_notebook(s, seed=2000 + i, n_cells=8, think_scale=0.15)
        lats += [r.latency_s for r in s.engine.metrics.interactions]
    return float(np.mean(lats))


def scheduler_ablation() -> Dict[str, float]:
    """Eq 1's point: prioritise sources that 'influence as many expensive
    downstream operators as possible'.  The scenario specifies cheap shallow
    dead-ends FIRST, then a deep chain the next interaction extends; FIFO
    burns think time on the dead-ends, utility runs the chain."""
    from repro.core import InteractionPredictor

    def scenario(policy, predictor=None):
        cat = make_catalog(seed=0)
        s = Session(catalog=cat, mode="sim", policy=policy, predictor=predictor)
        eng = s.engine
        lats = []
        for rep in range(4):
            # 6 shallow dead-ends, specified first (each 2 s)
            for i in range(6):
                eng.add("synthetic", kwargs={"cost_s": 2.0, "n_units": 4,
                                             "tag": f"dead{rep}_{i}"})
            # one deep chain (4 × 2 s) that the interaction will extend
            chain = None
            for i in range(4):
                chain = eng.add(
                    "synthetic", parents=[chain] if chain else [],
                    kwargs={"cost_s": 2.0, "n_units": 4,
                            "tag": f"chain{rep}_{i}"},
                )
            s.think(9.0)  # enough for ~the chain OR half the dead-ends
            probe = eng.add("synthetic", parents=[chain],
                            kwargs={"cost_s": 0.2, "tag": f"show{rep}"})
            eng.display(probe)
            lats.append(eng.metrics.interactions[-1].latency_s)
        return round(float(np.mean(lats)), 4)

    out = {}
    for policy in ("utility", "fifo", "lifo", "random", "cheapest"):
        out[policy] = scenario(policy)
    pred = InteractionPredictor()
    # the predictor learns that 'synthetic' chains lead to interactions
    out["utility_p(eq4)"] = scenario("utility_p", predictor=pred)
    out["notebook_corpus_utility"] = round(_mean_latency("utility"), 4)
    out["notebook_corpus_fifo"] = round(_mean_latency("fifo"), 4)
    return out


def cache_ablation(budget_mb: float = 2.0) -> Dict[str, Dict[str, float]]:
    out = {}
    for policy in ("paper_eq3", "corrected", "lru", "size"):
        lats, hits, miss, evs = [], 0, 0, 0
        for i in range(N_NOTEBOOKS):
            cat = make_catalog(seed=0)
            s = Session(
                catalog=cat, mode="sim", cache_policy=policy,
                budget_bytes=int(budget_mb * 2**20),
            )
            run_notebook(s, seed=3000 + i, n_cells=6)
            lats += [r.latency_s for r in s.engine.metrics.interactions]
            st = s.engine.cache.stats()
            hits += st["hits"]
            miss += st["misses"]
            evs += st["evictions"]
        out[policy] = {
            "mean_latency_s": round(float(np.mean(lats)), 4),
            "evictions": evs,
        }
    return out


def partition_ablation() -> Dict[str, Dict[str, float]]:
    """Fixed coarse (4) / fixed fine (64) / think-time-aware partition plans:
    measure interaction latency and preemption-lost work."""
    out = {}
    for mode in ("aware", "coarse4", "fine64"):
        lats, lost = [], 0
        for i in range(N_NOTEBOOKS):
            cat = make_catalog(seed=0)
            s = Session(catalog=cat, mode="sim")
            if mode != "aware":
                n = 4 if mode == "coarse4" else 64
                orig = s.read_table  # monkey-patch the partition plan

                def read(name, _s=s, _n=n, _orig=orig):
                    df = _orig(name)
                    spec = _s.catalog.spec(name)
                    df.node.kwargs["partition_bounds"] = uniform_partitions(
                        spec.nrows, _n
                    )
                    return df

                s.read_table = read
            run_notebook(s, seed=4000 + i, n_cells=6)
            lats += [r.latency_s for r in s.engine.metrics.interactions]
            lost += s.engine.executor.stats.units_preempted_lost
        out[mode] = {
            "mean_latency_s": round(float(np.mean(lats)), 4),
            "units_lost_to_preemption": lost,
        }
    return out


def speculation_ablation() -> Dict[str, Dict[str, float]]:
    """The paper's §5.2 scenario: the user re-runs a filter with different
    constants under *memory pressure* — speculation pins the pre-filter
    intermediate against eviction, so each tweak reuses it instead of
    recomputing the whole chain."""
    out = {}
    for spec_on in (True, False):
        lats = []
        hits = 0
        for i in range(N_NOTEBOOKS):
            cat = make_catalog(seed=0)
            s = Session(
                catalog=cat, mode="sim", speculation=spec_on,
                budget_bytes=900_000,  # fits the parent + a little
                cache_policy="lru",
            )
            df = s.read_table("events")
            df["z"] = df["a"] * 2.0
            rng = np.random.default_rng(i)
            for t in range(6):  # literal-tweaking loop
                flt = df[df["z"] > float(rng.uniform(0, 200))]
                s.show(flt.describe())
                # cache-filling side work between tweaks (memory pressure)
                other = s.read_table("users")
                other["w"] = other["a"] * float(rng.uniform(1, 2))
                s.show(other.describe())
                s.think(0.8)
            lats += [
                r.latency_s
                for j, r in enumerate(s.engine.metrics.interactions)
                if j % 2 == 0  # the filter interactions
            ]
            hits += s.engine.speculation.hits
        out["on" if spec_on else "off"] = {
            "mean_latency_s": round(float(np.mean(lats)), 4),
            "speculation_hits": hits,
        }
    return out


def serving_ablation() -> Dict[str, Dict[str, float]]:
    """Opportunistic serving (beyond-paper): anticipated prompts prefilled
    during think time vs cold requests."""
    from repro.configs import get_smoke_config
    from repro.models import ShardCtx, init_model
    from repro.serve import OpportunisticServer

    cfg = get_smoke_config("smollm_360m")
    params = init_model(cfg, ShardCtx(), seed=0)
    rng = np.random.default_rng(0)
    prompts = [tuple(int(x) for x in rng.integers(0, cfg.vocab, 24)) for _ in range(6)]

    cold = OpportunisticServer(cfg, params, step_cost_s=0.05, prefill_cost_s=0.1)
    for p in prompts:
        cold.request(p, n_tokens=4)
        cold.think(8.0)
    cold_lat = float(
        np.mean([r.latency_s for r in cold.metrics.interactions])
    )

    warm = OpportunisticServer(cfg, params, step_cost_s=0.05, prefill_cost_s=0.1)
    for i, p in enumerate(prompts):
        if i + 1 < len(prompts):
            warm.anticipate(prompts[i + 1])  # predicted next request
        warm.request(p, n_tokens=4)
        warm.think(8.0)
    warm_lat = float(
        np.mean([r.latency_s for r in warm.metrics.interactions])
    )
    return {
        "cold": {"mean_latency_s": round(cold_lat, 4)},
        "anticipated": {"mean_latency_s": round(warm_lat, 4)},
        "speedup": {"x": round(cold_lat / max(warm_lat, 1e-9), 2)},
    }


def run_all():
    rows = []
    for name, fn in (
        ("scheduler_policies", scheduler_ablation),
        ("cache_eviction", cache_ablation),
        ("partitioning", partition_ablation),
        ("speculation", speculation_ablation),
        ("opportunistic_serving", serving_ablation),
    ):
        t0 = time.perf_counter()
        out = fn()
        us = (time.perf_counter() - t0) * 1e6
        rows.append((name, us, out))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, us, out in run_all():
        print(f"{name},{us:.0f},{out}")
