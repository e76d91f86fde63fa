"""The benchmark: one cell of ``BENCHMARK.json`` per run of ``run.py``.

The serving program under test lives in ``src/``; importing this package
puts it on the path, as ``run.py`` does when it is started directly.
"""

import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
