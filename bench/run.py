"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell's tables from ``--seed``, warms up every program the
window runs, measures for ``--seconds``, checks what was shown against the
plain reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
``breakdown`` (traced runs) and ``checks`` (each compared number with its
limit, also the last lines of standard error).  Set-up is split on an
earlier ``setup`` line.  Without a TPU of a kind in ``bench/peaks.json``, or
with fewer chips than the cell needs, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the package, not this directory: bench/trace.py must not stand in for
    # the standard library's trace module
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    import jax

    # the cache lives at a fixed path inside the checkout, whatever
    # JAX_COMPILATION_CACHE_DIR says, and takes every program however fast it
    # compiled, so that only a checkout's first run compiles
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_cell(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                                  T_START)
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
