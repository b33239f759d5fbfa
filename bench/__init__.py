"""The on-chip benchmark of the lazy fusion runtime.

One command runs one cell once (``python3 bench/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``).  Everything a cell is made of is
found by name from ``BENCHMARK.json``: its configuration in
``configs/<config>.json``, its traffic mix in ``traffic/<traffic>.json``,
the system that drives it in ``systems/<system>.py``, the program and the
plain reference its configuration names in ``programs/`` and
``references/``, and each per-layer metric's reader in
``metrics/<metric>.py``.  A new cell, configuration or metric is new files
and new entries; no file here needs an edit for it.
"""
