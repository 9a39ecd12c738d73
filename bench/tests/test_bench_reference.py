"""The reference agrees with the engine on tiny tables on the CPU, through
the same authoring and serving path a run drives, on every backend the CPU
runs; its bfloat16 control does not."""
import time

import pytest

from bench import check, control, harness, tables, traffic
from bench.program import BenchCatalog
from bench.reference import Reference

from .conftest import tiny

PER_ANALYST = 8


def shown_and_reference(cell_name, backend, seed=11):
    cell = tiny(cell_name, backend)
    data = tables.make_tables(cell.config, seed)
    drv = harness.Driver(BenchCatalog(data), cell.config["engine"])
    drv.load_tables()
    shown = [drv.issue(a.name, it, time.perf_counter())
             for a in traffic.generate(cell.mix, seed)
             for it in a.interactions[:PER_ANALYST]]
    return cell, data, shown


def compare_all(data, shown):
    ref = Reference(data)
    mism, worst = 0, 0.0
    for s in shown:
        m, w = check.compare(s.table, ref.evaluate(s.item.recipe, s.item.action))
        mism, worst = mism + m, max(worst, w)
    return mism, worst


@pytest.mark.parametrize("backend", ["numpy", "xla", "interpret"])
def test_engine_matches_reference(cell_name, backend):
    cell, data, shown = shown_and_reference(cell_name, backend)
    assert not [s.error for s in shown if s.error]
    assert {s.item.template for s in shown} >= (
        {"q1", "q6", "q14", "top10", "shipmode"} if cell_name == "lineitem.adhoc"
        else {"describe", "value_counts"})
    mism, worst = compare_all(data, shown)
    assert mism == 0
    assert worst <= cell.limits["stat_rel_err"]


def test_bfloat16_control_fails(cell_name):
    cell = tiny(cell_name)
    got = control.readings(cell, 11, 3.0)
    assert got["compared"] > 0
    assert not check.within(got, cell.limits), got
