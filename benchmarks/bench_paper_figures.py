"""Paper §3 opportunity analysis, reproduced over synthetic corpora.

One function per figure:

* Fig 3  — think-time distribution (P50/P75 across cells, per-notebook medians)
* Fig 4  — # non-critical operators specified before each interaction (μ, σ)
* Fig 5  — fraction of head/tail interactions per notebook (μ, σ)
* Fig 6  — # operators that can benefit from reuse (μ, median, σ)

Paper reference values: Fig 3 P75 = 23 s; Fig 4 μ=4,σ=5 (Data100) / μ=7,σ=11
(Github); Fig 5 μ=0.04..0.11; Fig 6 median 3, μ=5..7.
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List

import numpy as np

sys.path.insert(0, "src")

from repro.core import ThinkTimeModel, count_non_critical_before  # noqa: E402
from repro.core.dag import DEFAULT_INTERACTION_OPS  # noqa: E402

from .workloads import corpus  # noqa: E402

N_NOTEBOOKS = 8


def fig3_think_time() -> Dict[str, float]:
    rng = np.random.default_rng(0)
    model = ThinkTimeModel()
    samples = model.sample(rng, 4000)
    per_nb_medians = [
        float(np.median(model.sample(np.random.default_rng(i), 30)))
        for i in range(200)
    ]
    return {
        "p50_s": float(np.percentile(samples, 50)),
        "p75_s": float(np.percentile(samples, 75)),
        "p90_s": float(np.percentile(samples, 90)),
        "median_of_nb_medians_s": float(np.median(per_nb_medians)),
        "paper_p75_s": 23.0,
    }


def fig4_noncritical(nbs=None) -> Dict[str, float]:
    nbs = nbs or corpus(N_NOTEBOOKS)
    counts: List[int] = []
    for session, _trace in nbs:
        dag = session.engine.dag
        for it in dag.interactions():
            counts.append(count_non_critical_before(dag, it))
    return {
        "mean": float(np.mean(counts)),
        "std": float(np.std(counts)),
        "median": float(np.median(counts)),
        "frac_interactions_with_noncritical": float(np.mean(np.array(counts) > 0)),
        "paper_mean_data100": 4.0,
        "paper_mean_github": 7.0,
    }


def fig5_headtail(nbs=None) -> Dict[str, float]:
    nbs = nbs or corpus(N_NOTEBOOKS)
    fracs = []
    for session, _trace in nbs:
        its = session.engine.dag.interactions()
        if not its:
            continue
        ht = sum(1 for n in its if n.op in ("head", "tail"))
        fracs.append(ht / len(its))
    return {
        "mean": float(np.mean(fracs)),
        "std": float(np.std(fracs)),
        "paper_mean_data100": 0.04,
        "paper_mean_github": 0.11,
    }


FRAME_CHAIN_OPS = {
    "read_table", "filter", "filter_cmp", "isin", "between", "assign",
    "dropna", "fillna", "join", "sort_values", "drop_sparse_cols",
}


def fig6_reuse(nbs=None) -> Dict[str, float]:
    """Operators shared by multiple interactions' critical paths *but not
    stored as a variable by the user* (the paper's caveat): frame-lineage ops
    are variable-bound in our fluent frontend, so the reuse opportunity the
    paper counts is the shared inline subexpressions (projections, scalar
    aggregates like data.mean().mean(), …)."""
    nbs = nbs or corpus(N_NOTEBOOKS)
    reuse_counts = []
    for session, _trace in nbs:
        dag = session.engine.dag
        its = dag.interactions()
        used_by: Dict[int, int] = {}
        for it in its:
            for n in dag.ancestors(it, include_self=False):
                if n.op in FRAME_CHAIN_OPS:
                    continue
                used_by[n.nid] = used_by.get(n.nid, 0) + 1
        reuse_counts.append(sum(1 for c in used_by.values() if c >= 2))
    return {
        "mean": float(np.mean(reuse_counts)),
        "median": float(np.median(reuse_counts)),
        "std": float(np.std(reuse_counts)),
        "paper_median": 3.0,
    }


def run_all() -> List[tuple]:
    rows = []
    nbs = corpus(N_NOTEBOOKS)
    for name, fn, needs in (
        ("fig3_think_time", fig3_think_time, False),
        ("fig4_noncritical", fig4_noncritical, True),
        ("fig5_headtail", fig5_headtail, True),
        ("fig6_reuse", fig6_reuse, True),
    ):
        t0 = time.perf_counter()
        out = fn(nbs) if needs else fn()
        us = (time.perf_counter() - t0) * 1e6
        rows.append((name, us, out))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, us, out in run_all():
        print(f"{name},{us:.0f},{out}")
