"""Fixtures of the benchmark's own tests: tiny cells that run on the CPU."""
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import pytest  # noqa: E402

from bench import harness  # noqa: E402

# Each mix with the configuration it runs over.  ``notebook.think`` and
# ``lineitem.adhoc`` are no cells of BENCHMARK.json; their mixes keep the
# generator's think times and template kind tested for a later cell, under
# the Run All cell's limits (same configuration, same numbers compared).
CELLS = ("notebook.runall", "notebook.think", "lineitem.adhoc")
MIXES = {"notebook.runall": "notebook_runall", "notebook.think": "notebook_think",
         "lineitem.adhoc": "tpch_adhoc"}


def cell_of(name: str) -> harness.Cell:
    """A cell of BENCHMARK.json, or one built from its mix file."""
    try:
        return harness.load_cell(name)
    except KeyError:
        base = harness.load_cell("notebook.runall")
        mix = harness.load_json(harness.BENCH / "traffic" / f"{MIXES[name]}.json")
        return harness.Cell(name=name, chips=1, config=base.config, mix=mix,
                            limits=base.limits, bench=base.bench)


def tiny(name: str, backend: str = "numpy", rows_div: int = 4096) -> harness.Cell:
    """The cell at a size a test run holds: tables cut by ``rows_div`` (a
    foreign key follows its dimension), four analysts thinking for tenths of
    a second (or not at all, where the mix does not think), at most 48
    interactions each, every interaction checked."""
    cell = cell_of(name)
    for t in cell.config["tables"]:
        t["nrows"] = max(int(t["nrows"]) // rows_div, 64)
    cell.config["engine"]["kernel_backend"] = backend
    n = min(int(cell.mix["interactions_per_analyst"]), 48)
    cell.mix.update(analysts=4, stagger_s=0.2, check_sample=1000, interactions_per_analyst=n)
    if cell.mix["think"] is not None:
        cell.mix["think"] = {"median_s": 0.05, "p75_s": 0.2}
    return cell


@pytest.fixture
def no_chip_look(monkeypatch):
    """Runs on the CPU: the harness takes whatever devices JAX has."""
    import jax

    monkeypatch.setattr(harness, "check_devices", lambda chips, peaks: jax.devices())


@pytest.fixture(params=CELLS)
def cell_name(request):
    return request.param
