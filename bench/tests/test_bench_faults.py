"""Whole runs of each cell on the CPU, without the harness's look for a
chip: sound, ``correct`` is true; with the timed path broken underneath,
it comes out false.  The faults a one-chip dataframe cell can have:

* an answer altered where it is produced (a describe's mean, 1% off);
* half of the batch left out, the mean taken over the rest (the describe
  combine merges only the first half of the partitions' partials).
"""
import time

import pytest

from bench import harness
from repro.frame import blocking

from .conftest import tiny

SECONDS = 3.0


def run(cell_name, traced=False):
    return harness.run_cell(tiny(cell_name), 2**31 + 17, SECONDS, traced,
                            time.perf_counter())


def test_sound_run_is_correct(cell_name, no_chip_look):
    r = run(cell_name, traced=cell_name == "notebook.runall")
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    names = set(r["metrics"])
    assert ("setup_s" in names) != ("device_idle_share" in " ".join(names))


def altered_mean(merge):
    def broken(parts):
        out = merge(parts)
        for s in out.values():
            s.mean *= 1.01
        return out
    return broken


def half_the_batch(merge):
    def broken(parts):
        parts = list(parts)
        return merge(parts[: max(1, len(parts) // 2)])
    return broken


@pytest.mark.parametrize("fault", [altered_mean, half_the_batch])
def test_broken_path_is_not_correct(cell_name, fault, monkeypatch, no_chip_look):
    monkeypatch.setattr(blocking, "merge_stats", fault(blocking.merge_stats))
    r = run(cell_name)
    assert not r["correct"], r["checks"]
