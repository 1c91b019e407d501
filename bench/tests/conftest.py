"""Tests of the benchmark's own code, on the CPU at small sizes.

Run from the repository root:  python -m pytest -q bench/tests

The small configurations (``small.py``) run the dense block, whose module
``small.DENSE`` is, and the tests hand it to the harness as ``bench/run.py``
hands it the module a configuration names.  ``test_arch.py`` pins that
module's numbers and runs a cell under a module of its own.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
