"""The one traffic generator: turns a mix file (``traffic/<mix>.json``) and a
seed into each analyst's interactions and think times, as plain data.

An interaction is a frame *recipe* (a base table and the steps that derive
a frame from it), an *action* that shows something of the frame, and whether
it is shown progressively.  Recipes are tuples, so equal recipes compare and
hash equal: the reference memoises on them, the program builds them through
its own API.  Steps:

* ``("read", table)``
* ``("where", ((col, cmp, value), ...))`` — one filter, the conjunction of
  its terms; ``cmp`` is gt/ge/lt/le/eq/ne, or ``between`` with ``value`` a
  ``(lo, hi)`` pair (both ends included)
* ``("assign_mul", out, col, factor)`` — ``df[out] = df[col] * factor``
* ``("assign_prod", out, col1, col2)`` — ``df[out] = df[col1] * df[col2]``
* ``("fillna_mean", col)`` — ``df[col] = df[col].fillna(df[col].mean())``
* ``("dropna", col)`` — ``df.dropna(subset=[col])``
* ``("join", table, on)`` — inner join with a dimension table on ``on``

Actions: ``("describe",)``, ``("describe_cols", cols)``, ``("head", k)``,
``("tail", k)``, ``("value_counts", col)``, ``("columns",)``,
``("groupby_head", by, fn, k)``, ``("groupby", by, ((col, fn), ...))`` and
``("topk", col, k, ascending)``.

Two kinds of mix:

* ``notebook``: the paper's notebook process (arXiv:2103.02145 §3, Fig. 5),
  copied from the repository's paper-figure generator: each cell adds one or
  two specification steps to one of the notebook's frames (or reads a new
  one) and ends in one interaction drawn from the mix;
* ``templates``: independent parameterised queries; each template's share is
  fixed per position across analysts, its parameters drawn per interaction.

Think times follow the lognormal prior of the paper's Data 100 notebooks
(§3.1: median 6 s, P75 23 s), or are zero where the mix's ``think`` is null.  So that every seed gets the same work and
arrivals in another order, the k-th think time of the analysts is one
stratified draw of that distribution, the quantiles (i + 0.5) / K, permuted
by the seed; the analysts' start offsets are stratified the same way over
``stagger_s``; and each analyst runs one of a fixed set of scripts (its
notebooks, or its templates with their parameters), dealt out by the seed.
The tables still come from the seed, so a script's filters keep other rows,
as many to rounding.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from .tables import seed_words

# The analysts' scripts come from this fixed seed; a run's seed deals them out.
SCRIPT_SEED = 0
_Z75 = NormalDist().inv_cdf(0.75)


@dataclass(frozen=True)
class Interaction:
    template: str
    recipe: Tuple
    action: Tuple
    progressive: bool = False


@dataclass
class Analyst:
    name: str
    start_s: float
    interactions: List[Interaction] = field(default_factory=list)
    thinks: List[float] = field(default_factory=list)  # thinks[i] follows i


def _rng(seed: int, *words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(seed_words(seed) + list(words)))


def _tuple(x: Any) -> Any:
    return tuple(_tuple(v) for v in x) if isinstance(x, list) else x


def draw(spec: Any, rng: np.random.Generator, params: Dict[str, Any]) -> Any:
    """Resolve one value of a mix file: ``{"uniform": [lo, hi]}``,
    ``{"int": [lo, hi]}`` (hi excluded), ``{"choice": [...]}`` or
    ``{"param": name, "scale": s, "plus": c}``; lists resolve element-wise,
    anything else is literal."""
    if isinstance(spec, dict):
        if "uniform" in spec:
            lo, hi = spec["uniform"]
            return float(rng.uniform(lo, hi))
        if "int" in spec:
            lo, hi = spec["int"]
            return int(rng.integers(lo, hi))
        if "choice" in spec:
            return _tuple(spec["choice"][int(rng.integers(len(spec["choice"])))])
        if "param" in spec:
            value = params[spec["param"]] * spec.get("scale", 1) + spec.get("plus", 0)
            return round(value, 12) if isinstance(value, float) else value
        raise ValueError(f"unknown draw {spec!r}")
    if isinstance(spec, list):
        return tuple(draw(v, rng, params) for v in spec)
    return spec


def think_quantile(think: dict, u: float) -> float:
    mu = math.log(think["median_s"])
    sigma = (math.log(think["p75_s"]) - mu) / _Z75
    return math.exp(mu + sigma * NormalDist().inv_cdf(u))


def stratified(rng: np.random.Generator, n: int) -> np.ndarray:
    """The quantile levels (i + 0.5) / n, permuted."""
    return (rng.permutation(n) + 0.5) / n


def balanced(rng: np.random.Generator, weights: Sequence[float], n: int) -> List[int]:
    """n choices whose counts follow ``weights`` (largest remainder), permuted."""
    w = np.asarray(weights, dtype=float) / float(sum(weights))
    counts = np.floor(w * n).astype(int)
    ties = rng.permutation(len(w))  # equal remainders: the seed picks
    rest = ties[np.argsort(counts[ties] - w[ties] * n, kind="stable")]
    counts[rest[: n - int(counts.sum())]] += 1
    picks = np.repeat(np.arange(len(w)), counts)
    return [int(i) for i in rng.permutation(picks)]


def _notebook(mix: dict, rng: np.random.Generator) -> List[Interaction]:
    """One notebook of the paper's process (after ``run_notebook`` of the
    repository's ``benchmarks/workloads.py``)."""
    tables = mix["tables"]
    frames: List[Tuple] = []
    out: List[Interaction] = []

    def new_frame() -> None:
        frames.append((("read", tables[int(rng.integers(0, len(tables)))]),))

    op_weights = np.cumsum([op["weight"] for op in mix["spec_ops"]])
    kinds = mix["interactions"]
    kind_p = np.array([k["weight"] for k in kinds], dtype=float)
    kind_p /= kind_p.sum()
    lo, hi = mix["spec_ops_per_cell"]
    new_frame()
    for _ in range(int(mix["cells_per_notebook"])):
        if rng.random() < mix["new_frame_p"] or not frames:
            new_frame()
        fidx = int(rng.integers(0, len(frames)))
        df = frames[fidx]
        for _ in range(int(rng.integers(lo, hi + 1))):
            roll = rng.random() * op_weights[-1]
            op = mix["spec_ops"][int(np.searchsorted(op_weights, roll, side="right"))]
            step = draw(op["step"], rng, {})
            if step[0] == "new_frame":
                new_frame()
            else:
                df = df + (step,)
        frames[fidx] = df
        kind = kinds[int(rng.choice(len(kinds), p=kind_p))]
        action = draw(kind["action"], rng, {})
        out.append(Interaction(action[0], df, action, bool(kind.get("progressive"))))
    return out


def _template(t: dict, rng: np.random.Generator) -> Interaction:
    params: Dict[str, Any] = {}
    for name, spec in t.get("params", {}).items():
        params[name] = draw(spec, rng, params)
    recipe = draw(t["frame"], rng, params)
    action = draw(t["action"], rng, params)
    return Interaction(t["name"], recipe, action, bool(t.get("progressive")))


def due_within(analysts: List[Analyst], seconds: float) -> List[List[Interaction]]:
    """Each analyst's interactions that fall due within ``seconds`` of the
    window opening were every answer instant: a superset of what a window of
    that length issues, since an answer's wait only puts later ones off."""
    out = []
    for a in analysts:
        due, items = a.start_s, []
        for item, think in zip(a.interactions, a.thinks):
            if due >= seconds:
                break
            items.append(item)
            due += think
        out.append(items)
    return out


def generate(mix: dict, seed: int) -> List[Analyst]:
    """Every analyst's interactions and think times for one run."""
    k = int(mix["analysts"])
    n = int(mix["interactions_per_analyst"])
    rng = _rng(seed)
    starts = stratified(rng, k) * float(mix["stagger_s"])
    think = mix["think"]  # None: every answer is followed at once ("Run All")
    thinks = [[think_quantile(think, u) if think else 0.0 for u in stratified(rng, k)]
              for _ in range(n)]
    analysts = [Analyst(f"analyst{i:03d}", float(starts[i])) for i in range(k)]
    slots = rng.permutation(k)
    scripts = _scripts(mix, k, n)
    for i, a in enumerate(analysts):
        units = scripts[int(slots[i])]
        if mix["kind"] == "notebook":  # the seed orders an analyst's notebooks too
            units = [units[u] for u in _rng(seed, 1, i).permutation(len(units))]
        a.interactions = [it for unit in units for it in unit][:n]
        a.thinks = [thinks[j][i] for j in range(n)]
    return analysts


def _scripts(mix: dict, k: int, n: int) -> List[List[List[Interaction]]]:
    """The ``k`` fixed scripts of at least ``n`` interactions that a run's
    seed deals out to its analysts, each as a list of units that stay
    together (a notebook, or one template): a script's filters decide how
    many rows its frames keep, so scripts drawn per seed would change the
    work per seed."""
    if mix["kind"] == "notebook":
        out = []
        for slot in range(k):
            notebooks: List[List[Interaction]] = []
            while sum(map(len, notebooks)) < n:
                notebooks.append(_notebook(mix, _rng(SCRIPT_SEED, slot, len(notebooks))))
            out.append(notebooks)
        return out
    if mix["kind"] == "templates":
        weights = [t["weight"] for t in mix["templates"]]
        prng = _rng(SCRIPT_SEED)
        picks = [balanced(prng, weights, k) for _ in range(n)]
        return [[[_template(mix["templates"][picks[j][slot]], _rng(SCRIPT_SEED, slot, j))]
                 for j in range(n)] for slot in range(k)]
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")
