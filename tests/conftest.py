import os
import sys

# single-device tests: do NOT force host devices here (test_dist.py's
# subprocesses do)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest

from repro.frame import Catalog, ColSpec, Session, TableSpec
from repro.kernels import ops


@pytest.fixture(params=["xla", "interpret"])
def ops_backend(request):
    """Each kernel-op case under the xla backend and under Pallas interpret
    mode, scoped to the test's thread."""
    with ops.local_backend(request.param):
        yield request.param


@pytest.fixture()
def catalog() -> Catalog:
    cat = Catalog()
    cat.register(
        TableSpec(
            "small",
            nrows=5_000,
            cols=(
                ColSpec("x", low=0.0, high=10.0),
                ColSpec("y", null_frac=0.2),
                ColSpec("k", kind="cat", n_categories=7),
                ColSpec("i", kind="int", low=0, high=100),
                ColSpec("j", kind="int", low=0, high=7),
            ),
            io_seconds=1.0,
            seed=7,
        )
    )
    cat.register(
        TableSpec(
            "large",
            nrows=200_000,
            cols=(ColSpec("a"), ColSpec("b", null_frac=0.3)),
            io_seconds=18.5,
            seed=11,
        )
    )
    cat.register(
        TableSpec(
            "dim",
            nrows=7,
            cols=(ColSpec("j", kind="key"), ColSpec("w")),
            io_seconds=0.01,
            seed=3,
        )
    )
    return cat


@pytest.fixture()
def session(catalog) -> Session:
    return Session(catalog=catalog, mode="sim")


def table_as_numpy(catalog: Catalog, name: str) -> dict:
    spec = catalog.spec(name)
    part = catalog.generate(name, 0, spec.nrows)
    return {n: part.columns[n].to_numpy() for n in part.order}
