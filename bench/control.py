"""The output check's control: the reference itself, computed in bfloat16
(every float input rounded, every sum accumulated in bfloat16), put in the
program's place and compared with the float64 reference on what a run
checks.  It has to come out not correct; its readings set the upper end of
each limit in ``limits/<cell>.json``.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 45]

For each seed it takes the interactions a run's window would issue were
every answer instant (due times from the think times alone), draws the
check's sample from them as a run does, and prints one JSON line of the
numbers compared.  It needs no chip and no program.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, seconds: float) -> dict:
    from . import check, tables, traffic
    from .harness import sample_keys
    from .reference import Reference

    t0 = time.perf_counter()
    data = tables.make_tables(cell.config)
    items = [it for script in traffic.due_within(traffic.generate(cell.mix, seed), seconds)
             for it in script]
    picked = sample_keys([(it, False) for it in items],
                         int(cell.mix["check_sample"]), seed)
    ref, ctl = Reference(data), Reference(data, "bfloat16")
    mism, worst = 0, 0.0
    for recipe, action in picked:
        m, w = check.compare(dict(ctl.evaluate(recipe, action).table),
                             ref.evaluate(recipe, action))
        mism, worst = mism + m, max(worst, w)
    return {"seed": seed, "compared": len(picked), "exact_mismatches": mism,
            "stat_rel_err": worst, "unanswered": 0,
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path[:1] = [str(ROOT)]
    from bench import control, harness

    cell = harness.load_cell(args.workload)
    seconds = args.seconds or float(cell.bench["run_seconds"])
    for seed in args.seeds:
        print(json.dumps(control.readings(cell, seed, seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
