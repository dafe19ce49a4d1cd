"""CPU tests of the chip benchmark: ``python -m pytest chipbench/tests``.

They run on the CPU at tiny sizes; the program's Pallas kernels run in
interpret mode there."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
