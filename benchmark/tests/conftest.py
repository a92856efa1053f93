"""The benchmark's own tests run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They describe no TPU topology and start no child process.  Tier-1's
``tests/`` does not collect this directory.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for _path in (HERE, BENCH, os.path.dirname(BENCH)):
    if _path not in sys.path:
        sys.path.insert(0, _path)
