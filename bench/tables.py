"""Seeded table data of a configuration, as plain numpy arrays.

The yardstick makes the data; the program and the reference read the same
arrays.  Every column is drawn in bulk from its own PCG64 stream, keyed by
(seed, table, column), so a seed always gives the same tables and one column
never shifts another.  A run makes its tables from ``DATA_SEED``, as TPC-H's
generator makes one dataset per scale factor: the program compiles a step
for each exact length a filter leaves, so tables drawn per run would make
every run compile anew; the run's own seed deals out the traffic.

Column kinds (``kind`` in a configuration's ``columns``):

* ``float``: uniform in [low, high), float64; ``null_frac`` of rows null;
* ``int``: uniform integer in [low, high), int64; with ``references`` (a
  table's name) a foreign key uniform over that table's keys [1, nrows];
* ``decimal``: a uniform integer in [low, high) divided by ``divisor``,
  float64 (TPC-H's discounts and taxes, in steps of 0.01);
* ``key``: the row index plus ``base``, int64 (unique keys of a dimension);
* ``cat``: uniform category code in [0, len(values)), int32, with its
  dictionary ``values`` (sorted, so codes order as the strings do): listed,
  every combination of the word lists of ``product`` joined by ``sep`` (a
  space unless given), or ``n_categories`` generated names;
* ``offset``: the int column ``of`` plus a uniform integer in [low, high];
* ``cat_by_cutoff``: where the int column ``of`` is at most ``cutoff`` a
  uniform choice among ``upto``, else among ``after`` (all of them in
  ``values``);
* ``tpch_retailprice``: TPC-H 4.2.3's P_RETAILPRICE of the key column ``of``;
* ``tpch_extendedprice``: TPC-H 4.2.3's L_EXTENDEDPRICE, the column
  ``quantity`` times the retail price of the part in column ``partkey``.

A column marked ``hidden`` is made, so later columns can derive from it, and
then dropped: it is not loaded.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


@dataclass
class TableData:
    name: str
    nrows: int
    order: List[str]
    data: Dict[str, np.ndarray]
    mask: Dict[str, Optional[np.ndarray]]  # True = valid; None = no nulls
    dictionary: Dict[str, Optional[np.ndarray]]  # cat columns only
    kinds: Dict[str, str]

    @property
    def nbytes(self) -> int:
        total = sum(a.nbytes for a in self.data.values())
        return total + sum(m.nbytes for m in self.mask.values() if m is not None)


def seed_words(seed: int) -> List[int]:
    """A seed of any size as non-negative 32-bit words for SeedSequence."""
    seed = int(seed)
    words = [1 if seed < 0 else 0]
    seed = abs(seed)
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def column_values(spec: dict) -> Optional[np.ndarray]:
    if spec["kind"] not in ("cat", "cat_by_cutoff"):
        return None
    if "product" in spec:
        sep = spec.get("sep", " ")
        values = [sep.join(words) for words in itertools.product(*spec["product"])]
    else:
        values = spec.get("values") or [
            f"{spec['name']}_{i:03d}" for i in range(int(spec["n_categories"]))
        ]
    return np.array(sorted(values), dtype=object)


def tpch_retailprice(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE = (90000 + ((P_PARTKEY/10) modulo 20001) + 100 *
    (P_PARTKEY modulo 1000)) / 100, with integer division by 10."""
    k = partkey.astype(np.int64)
    return (90000 + (k // 10) % 20001 + 100 * (k % 1000)) / 100.0


def _column(col: dict, rng: np.random.Generator, nrows: int, made: dict,
            nrows_of: Dict[str, int], dictionary) -> np.ndarray:
    kind = col["kind"]
    if kind == "float":
        lo, hi = float(col["low"]), float(col["high"])
        return lo + rng.random(nrows) * (hi - lo)
    if kind == "int":
        if "references" in col:
            lo, hi = 1, nrows_of[col["references"]] + 1
        else:
            lo, hi = int(col["low"]), int(col["high"])
        return rng.integers(lo, hi, nrows, dtype=np.int64)
    if kind == "decimal":
        k = rng.integers(int(col["low"]), int(col["high"]), nrows, dtype=np.int64)
        return k / float(col["divisor"])
    if kind == "key":
        return np.arange(nrows, dtype=np.int64) + int(col.get("base", 0))
    if kind == "cat":
        return rng.integers(0, len(dictionary), nrows, dtype=np.int32)
    if kind == "offset":
        return made[col["of"]] + rng.integers(int(col["low"]), int(col["high"]) + 1,
                                              nrows, dtype=np.int64)
    if kind == "cat_by_cutoff":
        code = {v: i for i, v in enumerate(dictionary)}
        upto = np.array([code[v] for v in col["upto"]], np.int32)
        after = np.array([code[v] for v in col["after"]], np.int32)
        pick = rng.integers(0, len(upto) * len(after), nrows)
        return np.where(made[col["of"]] <= int(col["cutoff"]),
                        upto[pick % len(upto)], after[pick % len(after)])
    if kind == "tpch_retailprice":
        return tpch_retailprice(made[col["of"]])
    if kind == "tpch_extendedprice":
        cents = made[col["quantity"]] * np.round(
            tpch_retailprice(made[col["partkey"]]) * 100).astype(np.int64)
        return cents / 100.0
    raise ValueError(f"unknown column kind {kind!r}")


def make_table(table: dict, seed: int, table_index: int, nrows: int,
               nrows_of: Optional[Dict[str, int]] = None) -> TableData:
    """One table's columns, each from its own (seed, table, column) stream."""
    data, mask, dictionary, kinds = {}, {}, {}, {}
    for ci, col in enumerate(table["columns"]):
        name = col["name"]
        rng = np.random.default_rng(
            np.random.SeedSequence(seed_words(seed) + [table_index, ci])
        )
        dictionary[name] = column_values(col)
        data[name] = _column(col, rng, nrows, data, nrows_of or {}, dictionary[name])
        null_frac = float(col.get("null_frac", 0.0))
        mask[name] = rng.random(nrows) >= null_frac if null_frac > 0 else None
        kinds[name] = "cat" if dictionary[name] is not None else \
            "float" if data[name].dtype.kind == "f" else "int"
    order = [c["name"] for c in table["columns"] if not c.get("hidden")]
    keep = set(order)
    return TableData(
        name=table["name"], nrows=nrows, order=order,
        data={n: v for n, v in data.items() if n in keep},
        mask={n: v for n, v in mask.items() if n in keep},
        dictionary={n: v for n, v in dictionary.items() if n in keep},
        kinds={n: v for n, v in kinds.items() if n in keep},
    )


DATA_SEED = 0


def make_tables(config: dict, seed: int = DATA_SEED) -> Dict[str, TableData]:
    """Every table of ``config`` from ``seed``."""
    nrows_of = {t["name"]: int(t["nrows"]) for t in config["tables"]}
    return {
        table["name"]: make_table(table, seed, ti, int(table["nrows"]), nrows_of)
        for ti, table in enumerate(config["tables"])
    }
