"""Chip benchmark of the opportunistic dataframe engine.

Run one cell once with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  ``BENCHMARK.json`` at the repository root
names the cells; everything that belongs to one configuration, traffic mix
or per-layer metric is a file of its own under this directory, found by
name:

* ``configs/<config>.json`` — the deployment: tables, columns, scale, engine
  settings, source, what was cut and what was assumed;
* ``traffic/<mix>.json`` — the parameters the one generator
  (``traffic.py``) reads;
* ``metrics/<metric>.py`` — the reader of one metric (or of one family that
  differs only in the cell suffix);
* ``limits/<cell>.json`` — the limit of each number the output check
  compares, with the readings it was set from.

``tables.py``, ``traffic.py``, ``reference/``, ``check.py``, ``trace.py`` and
``peaks.json`` are the yardstick: they import nothing of the program.
``program.py`` is the one module that drives the system under test.
"""
