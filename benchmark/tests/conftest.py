"""The benchmark's own tests: run by hand, never by ``pytest tests/``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They run on the CPU at sizes a test run can hold; nothing they print is
a device number.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
