"""The benchmark's one bridge to the system under test (``src/repro``).

It serves the yardstick's tables through the program's catalog interface,
authors each analyst's frames in a private ``Session`` through the public
frame API, and turns what the program shows into plain tables for the
check.  Everything else under ``bench/`` stays independent of the program.
"""
from __future__ import annotations

import operator
from typing import Any, Dict, Tuple

import numpy as np

from repro.frame import Catalog, ColSpec, Session, TableSpec
from repro.frame.table import Column, Partition

_CMP = {"gt": operator.gt, "ge": operator.ge, "lt": operator.lt,
        "le": operator.le, "eq": operator.eq, "ne": operator.ne}


class BenchCatalog(Catalog):
    """The program's catalog over the yardstick's arrays.  A partition is
    sliced once and handed out as the same object every time it is read, so
    a column uploaded to the device once stays uploaded across sessions, as
    a loaded table does in a deployment; the program pays no generation."""

    def __init__(self, tables):
        super().__init__()
        self.tables = tables
        self._parts: Dict[Tuple[str, int, int], Partition] = {}
        for t in tables.values():
            cols = tuple(ColSpec(n, kind=t.kinds[n]) for n in t.order)
            self.register(TableSpec(t.name, nrows=t.nrows, cols=cols))

    def generate(self, name: str, start: int, stop: int) -> Partition:
        key = (name, start, stop)
        part = self._parts.get(key)
        if part is None:
            t = self.tables[name]
            cols = {}
            for n in t.order:
                mask = t.mask[n]
                cols[n] = Column(
                    data=t.data[n][start:stop],
                    mask=None if mask is None else mask[start:stop],
                    dictionary=t.dictionary[n],
                )
            part = self._parts[key] = Partition(cols, list(t.order))
        return part


def build_frame(session: Session, recipe: Tuple):
    """A recipe (``traffic.py``) as frames of ``session``."""
    df = session.read_table(recipe[0][1])
    for step in recipe[1:]:
        op = step[0]
        if op == "where":
            pred = None
            for col, cmp, value in step[1]:
                term = (df[col].between(value[0], value[1]) if cmp == "between"
                        else _CMP[cmp](df[col], value))
                pred = term if pred is None else pred & term
            df = df[pred]
        elif op == "assign_mul":
            df[step[1]] = df[step[2]] * step[3]
        elif op == "assign_prod":
            df[step[1]] = df[step[2]] * df[step[3]]
        elif op == "fillna_mean":
            df[step[1]] = df[step[1]].fillna(df[step[1]].mean())
        elif op == "dropna":
            df = df.dropna(subset=[step[1]])
        elif op == "join":
            df = df.join(session.read_table(step[1]), on=step[2])
        else:
            raise ValueError(f"unknown step {op!r}")
    return df


def build_action(df, action: Tuple):
    """The node an action shows, on frame ``df``."""
    kind = action[0]
    if kind == "describe":
        shown = df.describe()
    elif kind == "describe_cols":
        shown = df[list(action[1])].describe()
    elif kind == "head":
        shown = df.head(int(action[1]))
    elif kind == "tail":
        shown = df.tail(int(action[1]))
    elif kind == "value_counts":
        shown = df[action[1]].value_counts()
    elif kind == "columns":
        shown = df.columns
    elif kind == "groupby_head":
        shown = df.groupby(action[1]).agg(action[2]).head(int(action[3]))
    elif kind == "groupby":
        shown = df.groupby(action[1]).agg({c: fn for c, fn in action[2]})
    elif kind == "topk":
        shown = df.sort_values(action[1], ascending=bool(action[3])).head(int(action[2]))
    else:
        raise ValueError(f"unknown action {kind!r}")
    return shown.node


def as_table(value: Any) -> Dict[str, np.ndarray]:
    """What the program showed, as ``{column: values}``."""
    if isinstance(value, list):  # df.columns
        return {"columns": np.array(list(value), dtype=object)}
    return value.to_pydict()
