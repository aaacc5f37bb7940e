"""The benchmark's own tests: run on the CPU with

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

from the root of a checkout.  They import the harness as a package and
the program from ``src/``."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
