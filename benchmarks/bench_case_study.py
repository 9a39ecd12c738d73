"""Paper §6 case study, reproduced end-to-end.

The Home-Credit-style notebook: read a large file; inspect `columns` and
`head()`; debug a drop-sparse-columns transform with a trailing `.head()`;
apply it; double-check `columns`.  Think times injected from the Fig 3
distribution (the paper's methodology).

Paper's reported numbers: read_csv 18.5 s eager; with opportunistic
evaluation the columns/head outputs appear in ~122 ms and the user's total
synchronous wait collapses to ~1.3 s + 2.3 s for the transform (paid once,
not twice).
"""
from __future__ import annotations

import sys
import time
from typing import Dict

import numpy as np

sys.path.insert(0, "src")

from repro.core import ThinkTimeModel  # noqa: E402
from repro.frame import Catalog, ColSpec, Session, TableSpec  # noqa: E402

READ_SECONDS = 18.5  # the paper's measured read_csv time

CELLS = [
    'data = pd.read_csv("application_train")',
    "data.columns",
    "data.head()",
    "data.drop_sparse_cols(0.8).head()",
    "data = data.drop_sparse_cols(0.8)",
    "data.columns",
]


def case_study_catalog() -> Catalog:
    cat = Catalog()
    cat.register(
        TableSpec(
            "application_train",
            nrows=307_511,  # the actual Kaggle table size
            cols=tuple(
                [ColSpec(f"c{i:02d}", null_frac=(0.6 if i % 4 == 0 else 0.05))
                 for i in range(24)]
            ),
            io_seconds=READ_SECONDS,
            seed=42,
        )
    )
    return cat


def run(opportunistic: bool = True, seed: int = 0) -> Dict[str, float]:
    rng = np.random.default_rng(seed)
    think = ThinkTimeModel()
    session = Session(
        catalog=case_study_catalog(), mode="sim", opportunistic=opportunistic
    )
    latencies = []
    for code in CELLS:
        session.cell(code)
        recs = session.engine.metrics.interactions
        latencies.append(recs[-1].latency_s if recs and code != CELLS[0] else 0.0)
        session.think(float(think.sample(rng)))
    m = session.engine.metrics
    return {
        "sync_wait_s": m.sync_wait_s,
        "first_output_latency_s": (
            m.interactions[0].latency_s if m.interactions else float("nan")
        ),
        "per_interaction_s": [round(r.latency_s, 4) for r in m.interactions],
        "think_s": m.think_s,
    }


def run_all():
    rows = []
    t0 = time.perf_counter()
    opp = run(opportunistic=True)
    us = (time.perf_counter() - t0) * 1e6
    rows.append(("case_study_opportunistic", us, opp))
    t0 = time.perf_counter()
    eager = run(opportunistic=False)
    us = (time.perf_counter() - t0) * 1e6
    rows.append(("case_study_eager", us, eager))
    rows.append(
        (
            "case_study_speedup",
            0.0,
            {
                "eager_sync_wait_s": round(eager["sync_wait_s"], 3),
                "opp_sync_wait_s": round(opp["sync_wait_s"], 3),
                "speedup": round(eager["sync_wait_s"] / max(opp["sync_wait_s"], 1e-9), 2),
                "paper_read_s": READ_SECONDS,
                "paper_first_output_ms": 122,
            },
        )
    )
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, us, out in run_all():
        print(f"{name},{us:.0f},{out}")
