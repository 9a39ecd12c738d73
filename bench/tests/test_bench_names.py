"""BENCHMARK.json keeps to the characters and keys the benchmark contract
allows, and every name in it finds its file."""
import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def line_ok(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= int(BENCH["run_seconds"]) <= 51
    assert all(PATH.match(p) and ".." not in p and not p.startswith("/")
               for p in BENCH["paths"])
    assert all(line_ok(w) for w in BENCH["command"]) and len(BENCH["command"]) <= 32


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entries(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for e in BENCH[section]:
        assert set(e) - {"workloads"} == ENTRY_KEYS[section], e["name"]
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e and section != "end_to_end" and section != "per_layer":
                assert line_ok(e[key]), (e["name"], key)
        if "layer" in e:
            assert line_ok(e["layer"])
        for key in ("config", "traffic"):
            if key in e:
                assert NAME.match(e[key])
        assert all(NAME.match(k) for k in e.get("reduced", []))


def test_metrics_have_readers_and_move_end_to_end_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
        if m["name"] != "setup_s":
            mod = importlib.import_module(f"bench.metrics.{m['name'].split('.')[0]}")
            assert callable(mod.read)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", cells)


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        assert cfg["name"] == w["config"]
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        limits = json.loads((ROOT / "bench" / "limits" / f"{w['name']}.json").read_text())
        assert set(limits["limits"]) == {"exact_mismatches", "unanswered", "stat_rel_err"}
        assert w["chips"] in (1, 4)
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
