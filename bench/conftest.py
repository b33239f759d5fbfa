"""Tiny sizes of the configurations added after ``tests/conftest.py``'s
``TINY`` was written, registered into it before any test runs, so that the
tiny checkout (``tests/conftest.py``'s ``tiny_root``) can cut every cell of
``BENCHMARK.json`` to CPU size.  Each configuration's sizes are a file
``tests/data/tiny_<name>.json``."""

from __future__ import annotations

import json
from pathlib import Path

from bench.tests import conftest as _tests

for _path in sorted((Path(__file__).parent / "tests" / "data")
                    .glob("tiny_*.json")):
    _tests.TINY.setdefault(_path.stem[len("tiny_"):],
                           json.loads(_path.read_text())["config"])
