"""Plain numpy evaluation of every recipe step and action of ``traffic.py``.

Semantics are pandas': comparisons with a null are False, arithmetic keeps
nulls, ``describe`` covers the non-string columns (count, mean, std with
ddof 1, min, max), ``value_counts`` orders by count then value, a groupby's
keys come out sorted, a sort is stable with nulls last, a join keeps the
left rows in order and appends the right columns (``_right`` on a clash).

``precision="bfloat16"`` is the output check's control: every float input is
rounded to bfloat16 and every sum is accumulated in bfloat16, pairwise.

Each result is a table ``{column: values}`` (numbers as float64 with NaN for
null, strings as objects with None) with ``approx``, the same shape of
booleans, marking the cells a float32 engine computes only to rounding:
means, standard deviations, sums, and anything derived from a fill value
that is itself a mean.  Every other cell is compared exactly.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

CMP = {
    "gt": np.greater, "ge": np.greater_equal, "lt": np.less,
    "le": np.less_equal, "eq": np.equal, "ne": np.not_equal,
}


@dataclass
class Col:
    data: np.ndarray
    valid: Optional[np.ndarray] = None  # None = all valid
    dictionary: Optional[np.ndarray] = None
    approx: Optional[np.ndarray] = None  # rows holding a computed fill value

    def valid_mask(self) -> np.ndarray:
        return np.ones(len(self.data), bool) if self.valid is None else self.valid

    def take(self, idx: np.ndarray) -> "Col":
        pick = (lambda a: None if a is None else a[idx])
        return Col(self.data[idx], pick(self.valid), self.dictionary, pick(self.approx))


class Frame:
    """Columns over row selections: a filter composes an index per source
    column and a column is gathered only when read, so rows of columns no
    action reads are never copied."""

    def __init__(self, order: List[str], sources: Dict[str, Tuple[Col, Optional[np.ndarray]]]):
        self.order = order
        self._sources = sources  # name -> (column, rows of it; None = all)
        self._cols: Dict[str, Col] = {}

    @classmethod
    def of(cls, order: List[str], cols: Dict[str, Col]) -> "Frame":
        return cls(list(order), {n: (c, None) for n, c in cols.items()})

    def col(self, name: str) -> Col:
        if name not in self._cols:
            c, idx = self._sources[name]
            self._cols[name] = c if idx is None else c.take(idx)
        return self._cols[name]

    def rows(self, name: str, idx: np.ndarray) -> Col:
        """Rows ``idx`` of a column, without gathering the rest of it."""
        if name in self._cols:
            return self._cols[name].take(idx)
        c, src = self._sources[name]
        return c.take(idx if src is None else src[idx])

    @property
    def nrows(self) -> int:
        c, idx = self._sources[self.order[0]]
        return len(c.data) if idx is None else len(idx)

    def take(self, rows: np.ndarray) -> "Frame":
        composed: Dict[int, np.ndarray] = {}
        sources = {}
        for n, (c, idx) in self._sources.items():
            if idx is None:
                sources[n] = (c, rows)
            else:
                if id(idx) not in composed:
                    composed[id(idx)] = idx[rows]
                sources[n] = (c, composed[id(idx)])
        return Frame(list(self.order), sources)

    def with_col(self, name: str, col: Col) -> "Frame":
        sources = dict(self._sources)
        sources[name] = (col, None)
        order = self.order + ([name] if name not in self.order else [])
        out = Frame(order, sources)
        out._cols = {n: c for n, c in self._cols.items() if n != name}
        return out


@dataclass
class Result:
    table: Dict[str, np.ndarray]
    approx: Dict[str, np.ndarray]


class Reference:
    """Evaluates interactions over the yardstick's tables
    (``tables.make_tables``), memoising frames along a shared recipe prefix."""

    def __init__(self, tables, precision: str = "float64"):
        if precision not in ("float64", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.tables = tables
        self.precision = precision
        self._memo: Dict[Tuple, Frame] = {}

    # -- arithmetic in the chosen precision ----------------------------------
    def _round(self, x: np.ndarray) -> np.ndarray:
        if self.precision == "float64" or x.dtype.kind != "f":
            return x
        import ml_dtypes

        return x.astype(ml_dtypes.bfloat16).astype(np.float64)

    def _sum(self, x: np.ndarray) -> float:
        if self.precision == "float64":
            return float(np.sum(x, dtype=np.float64))
        import ml_dtypes

        bf = ml_dtypes.bfloat16
        v = np.asarray(x, np.float64).astype(bf)
        while v.size > 1:
            if v.size % 2:
                v = np.append(v, bf(0))
            v = (v[0::2].astype(np.float32) + v[1::2].astype(np.float32)).astype(bf)
        return float(v[0]) if v.size else 0.0

    # -- frames ----------------------------------------------------------------
    def frame(self, recipe: Tuple) -> Frame:
        if recipe in self._memo:
            return self._memo[recipe]
        # keep only the frames on this recipe's path: memory stays bounded by
        # the recipe's depth when recipes come in sorted order
        self._memo = {k: v for k, v in self._memo.items() if recipe[: len(k)] == k}
        if len(recipe) == 1:
            out = self._read(recipe[0][1])
        else:
            out = self._step(self.frame(recipe[:-1]), recipe[-1])
        self._memo[recipe] = out
        return out

    def _read(self, name: str) -> Frame:
        t = self.tables[name]
        cols = {}
        for n in t.order:
            data = t.data[n]
            if t.kinds[n] == "float":
                data = self._round(data)
            cols[n] = Col(data, t.mask[n], t.dictionary[n])
        return Frame.of(t.order, cols)

    def _keep(self, f: Frame, terms: Sequence) -> np.ndarray:
        keep = np.ones(f.nrows, bool)
        for col, cmp, value in terms:
            c = f.col(col)
            if cmp == "between":
                hit = (c.data >= value[0]) & (c.data <= value[1])
            else:
                hit = CMP[cmp](c.data, value)
            keep &= hit & c.valid_mask()
        return keep

    def _step(self, f: Frame, step: Tuple) -> Frame:
        op = step[0]
        if op == "where":
            return f.take(np.flatnonzero(self._keep(f, step[1])))
        if op == "dropna":
            return f.take(np.flatnonzero(f.col(step[1]).valid_mask()))
        if op in ("assign_mul", "assign_prod"):
            a = f.col(step[2])
            if op == "assign_mul":
                data, valid, approx = a.data * step[3], a.valid, a.approx
            else:
                b = f.col(step[3])
                data = a.data * b.data
                valid = _and(a.valid, b.valid)
                approx = _or(a.approx, b.approx)
            return f.with_col(step[1], Col(self._round(data), valid, None, approx))
        if op == "fillna_mean":
            c = f.col(step[1])
            if c.valid is None:
                return f
            fill = self._sum(c.data[c.valid]) / max(int(c.valid.sum()), 1)
            return f.with_col(step[1], Col(np.where(c.valid, c.data, fill), None,
                                           c.dictionary, _or(c.approx, ~c.valid)))
        if op == "join":
            return self._join(f, self._read(step[1]), step[2])
        raise ValueError(f"unknown step {op!r}")

    def _join(self, left: Frame, right: Frame, on: str) -> Frame:
        rk = right.col(on)
        ridx = np.flatnonzero(rk.valid_mask())
        order = np.argsort(rk.data[ridx], kind="stable")
        rsorted, rrows = rk.data[ridx][order], ridx[order]
        if len(np.unique(rsorted)) != len(rsorted):
            raise ValueError("join: right keys must be unique")
        lk = left.col(on)
        pos = np.clip(np.searchsorted(rsorted, lk.data), 0, max(len(rsorted) - 1, 0))
        hit = (rsorted[pos] == lk.data) & lk.valid_mask() if len(rsorted) else \
            np.zeros(len(lk.data), bool)
        keep = np.flatnonzero(hit)
        out = left.take(keep)
        gather = rrows[pos[keep]]
        for n in right.order:
            if n != on:
                name = n if n not in out.order else f"{n}_right"
                out = out.with_col(name, right.col(n).take(gather))
        return out

    # -- actions ---------------------------------------------------------------
    def evaluate(self, recipe: Tuple, action: Tuple) -> Result:
        f = self.frame(recipe)
        kind = action[0]
        if kind == "describe":
            return self._describe(f, f.order)
        if kind == "describe_cols":
            return self._describe(f, list(action[1]))
        if kind == "head":
            return _rows(f, np.arange(min(int(action[1]), f.nrows)))
        if kind == "tail":
            k = min(int(action[1]), f.nrows)
            return _rows(f, np.arange(f.nrows - k, f.nrows))
        if kind == "value_counts":
            return self._value_counts(f, action[1])
        if kind == "columns":
            names = np.array(f.order, dtype=object)
            return Result({"columns": names}, {"columns": np.zeros(len(names), bool)})
        if kind == "groupby_head":
            by, fn, k = action[1], action[2], int(action[3])
            res = self._groupby(f, by, [(c, fn) for c in f.order if c != by])
            return Result({n: v[:k] for n, v in res.table.items()},
                          {n: v[:k] for n, v in res.approx.items()})
        if kind == "groupby":
            return self._groupby(f, action[1], [tuple(a) for a in action[2]])
        if kind == "topk":
            col, k, ascending = action[1], int(action[2]), bool(action[3])
            c = f.col(col)
            keys = np.where(c.valid_mask(), c.data.astype(np.float64),
                            np.inf if ascending else -np.inf)
            order = np.argsort(keys if ascending else -keys, kind="stable")
            return _rows(f, order[:k])
        raise ValueError(f"unknown action {kind!r}")

    def _describe(self, f: Frame, names: Sequence[str]) -> Result:
        table = {"stat": np.array(["count", "mean", "std", "min", "max"], dtype=object)}
        approx = {"stat": np.zeros(5, bool)}
        for n in names:
            c = f.col(n)
            if c.dictionary is not None:
                continue
            x = np.asarray(c.data if c.valid is None else c.data[c.valid], np.float64)
            cnt = x.size
            if cnt == 0:
                row = [0.0, 0.0, 0.0, np.inf, -np.inf]
            else:
                mean = self._sum(x) / cnt
                d = x - mean
                m2 = float(d @ d) if self.precision == "float64" else \
                    self._sum(self._round(d * d))
                std = float(np.sqrt(m2 / (cnt - 1))) if cnt > 1 else 0.0
                row = [float(cnt), mean, std, float(x.min()), float(x.max())]
            table[n] = np.array(row, dtype=np.float64)
            filled = c.approx is not None and bool(c.approx.any())
            approx[n] = np.array([False, True, True, filled, filled])
        return Result(table, approx)

    def _value_counts(self, f: Frame, col: str) -> Result:
        c = f.col(col)
        vals, inv = _group(c, c.valid_mask())
        counts = np.bincount(inv, minlength=len(vals))
        order = np.lexsort((vals, -counts))
        vals, counts = vals[order], counts[order]
        shown = c.dictionary[vals] if c.dictionary is not None else vals.astype(np.float64)
        return Result({col: shown, "count": counts.astype(np.float64)},
                      {col: np.zeros(len(vals), bool), "count": np.zeros(len(vals), bool)})

    def _groupby(self, f: Frame, by: str, aggs: List[Tuple[str, str]]) -> Result:
        key = f.col(by)
        kvalid = key.valid_mask()
        keys, inv = _group(key, kvalid)
        nk = len(keys)
        shown = key.dictionary[keys] if key.dictionary is not None else \
            keys.astype(np.float64)
        table = {by: shown}
        approx = {by: np.zeros(nk, bool)}
        for col, fn in aggs:
            c = f.col(col)
            v = c.data if key.valid is None else c.data[kvalid]
            if c.valid is not None:
                ok = c.valid if key.valid is None else c.valid[kvalid]
                v, ginv = v[ok], inv[ok]
            else:
                ginv = inv
            counts = np.bincount(ginv, minlength=nk).astype(np.float64)
            if fn in ("sum", "mean"):
                if self.precision == "float64":
                    sums = np.bincount(ginv, weights=v, minlength=nk)
                else:
                    sums = np.array([self._sum(v[ginv == g]) for g in range(nk)])
                table[col] = sums if fn == "sum" else np.divide(
                    sums, counts, out=np.full(nk, np.nan), where=counts > 0)
                approx[col] = np.ones(nk, bool)
                continue
            if fn == "count":
                out = counts
            elif fn in ("min", "max"):
                out = np.full(nk, np.inf if fn == "min" else -np.inf)
                (np.minimum if fn == "min" else np.maximum).at(out, ginv, v)
            else:
                raise ValueError(f"unknown aggregate {fn!r}")
            table[col] = out
            filled = c.approx is not None and bool(c.approx.any())
            approx[col] = np.full(nk, filled)
        return Result(table, approx)


def _group(c: Col, valid: np.ndarray):
    """Sorted distinct values of the valid rows and each row's group index;
    a category column counts its codes instead of sorting them."""
    data = c.data if c.valid is None else c.data[valid]
    if c.dictionary is None:
        return np.unique(data, return_inverse=True)
    present = np.flatnonzero(np.bincount(data, minlength=len(c.dictionary)))
    remap = np.zeros(len(c.dictionary), np.intp)
    remap[present] = np.arange(len(present))
    return present, remap[data]


def _and(a, b):
    if a is None:
        return b
    return a if b is None else a & b


def _or(a, b):
    if a is None:
        return b
    return a if b is None else a | b


def _rows(f: Frame, idx: np.ndarray) -> Result:
    table, approx = {}, {}
    for n in f.order:
        c = f.rows(n, idx)
        valid = c.valid_mask()
        if c.dictionary is not None:
            vals = c.dictionary[c.data].astype(object)
            vals[~valid] = None
        else:
            vals = c.data.astype(np.float64)
            vals[~valid] = np.nan
        table[n] = vals
        approx[n] = np.zeros(len(idx), bool) if c.approx is None else c.approx
    return Result(table, approx)
